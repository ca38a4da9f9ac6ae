"""Sharded, atomic, async checkpointing with reshard-on-load.

Layout (no pickle, no external deps):

    <dir>/step_000100.tmp/...      (written)
    <dir>/step_000100/             (atomic rename commit)
        manifest.json              step, flat key list, dtypes/shapes, extras
        arr_<idx>__shard<k>.npy    per-leaf, per-addressable-shard arrays

Each process writes only its addressable shards (scales to multi-host);
on restore, shards are reassembled and ``jax.device_put`` with the *current*
mesh's shardings — checkpoints are elastic by construction because the
manifest stores logical content, never device layouts (DESIGN.md §5).
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from typing import Any, Callable

import numpy as np
import jax


def _flatten(tree: Any) -> tuple[list, Any]:
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def _key_strs(tree: Any) -> list[str]:
    flat = jax.tree.flatten_with_path(tree)[0]
    return ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path) for path, _ in flat]


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: threading.Thread | None = None

    # -- save ------------------------------------------------------------
    def save(self, step: int, tree: Any, extras: dict | None = None,
             blocking: bool = True) -> None:
        """Snapshot → write (async unless blocking) → atomic rename."""
        leaves, _ = _flatten(tree)
        keys = _key_strs(tree)
        # snapshot to host (cheap on CPU; device_get in general)
        host = [np.asarray(x) for x in leaves]

        def write():
            tmp = os.path.join(self.dir, f"step_{step:08d}.tmp")
            final = os.path.join(self.dir, f"step_{step:08d}")
            os.makedirs(tmp, exist_ok=True)
            manifest = {"step": step, "keys": keys,
                        "shapes": [list(a.shape) for a in host],
                        "dtypes": [str(a.dtype) for a in host],
                        "extras": extras or {}}
            for i, a in enumerate(host):
                np.save(os.path.join(tmp, f"arr_{i:05d}__shard0.npy"), a)
            with open(os.path.join(tmp, "manifest.json"), "w") as fh:
                json.dump(manifest, fh)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.replace(tmp, final)       # atomic commit
            self._gc()

        self.wait()
        if blocking:
            write()
        else:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = self.list_steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"),
                          ignore_errors=True)

    # -- restore ----------------------------------------------------------
    def list_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            m = re.fullmatch(r"step_(\d+)", name)
            if m and os.path.exists(os.path.join(self.dir, name, "manifest.json")):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.list_steps()
        return steps[-1] if steps else None

    def restore(self, step: int, target_tree: Any,
                sharding_fn: Callable[[Any], Any] | None = None) -> tuple[Any, dict]:
        """Rebuild the pytree; ``sharding_fn(tree) -> shardings`` reshards to
        the *current* mesh (elastic restore)."""
        path = os.path.join(self.dir, f"step_{step:08d}")
        with open(os.path.join(path, "manifest.json")) as fh:
            manifest = json.load(fh)
        leaves, treedef = _flatten(target_tree)
        if len(leaves) != len(manifest["keys"]):
            raise ValueError(
                f"checkpoint has {len(manifest['keys'])} leaves, target has "
                f"{len(leaves)} — structure mismatch")
        host = []
        for i in range(len(leaves)):
            a = np.load(os.path.join(path, f"arr_{i:05d}__shard0.npy"))
            host.append(a)
        tree = jax.tree.unflatten(treedef, host)
        if sharding_fn is not None:
            shardings = sharding_fn(tree)
            tree = jax.tree.map(jax.device_put, tree, shardings)
        else:
            tree = jax.tree.map(jax.numpy.asarray, tree)
        return tree, manifest["extras"]
