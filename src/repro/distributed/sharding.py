"""Meshes and logical-axis sharding rules (MaxText-style).

``make_mesh`` builds the meshes the sharded search runs on, and
``logical_rules`` installs the active mesh that ``core.distributed`` reads
through ``get_mesh``.  ``shard`` annotates a tensor with *logical* axis
names, which a rules table maps to mesh axes (rules empty → no-op).

Conventions:
  batch        — global batch               → ("pod", "data")
  seq          — activation sequence        → None (train/prefill), "data" (SP)
  embed        — d_model features           → None for activations;
                                              FSDP axis for params ("data")
  heads/kv     — attention heads            → "model"
  mlp          — FFN hidden                 → "model"
  vocab        — vocabulary                 → "model"
  experts      — MoE experts                → "model"  (EP)
  cache_seq    — KV-cache sequence          → "model"  (flash-decoding split)
  layers       — stacked scan axis          → None
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def make_mesh(axis_shapes, axis_names) -> Mesh:
    """``jax.make_mesh`` with every axis explicitly ``Auto`` (GSPMD picks
    the shardings the rules do not pin)."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "act_seq": "model",             # inter-layer carry SP (used when
                                    # ArchConfig.act_shard == 'seq')
    "embed": None,
    "embed_fsdp": ("pod", "data"),    # parameter FSDP shard axis
    "heads": "model",
    "kv": None,                       # kv heads often < model size → replicate
    "q_per_kv": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_cap": None,
    "cache_seq": "model",
    "state": "model",                 # recurrent-state feature axis
    "conv": None,
    "layers": None,
    "frames": None,
    "patches": None,
}


def set_rules(mesh: Mesh | None, rules: dict[str, Any] | None) -> None:
    _state.mesh = mesh
    _state.rules = dict(rules) if rules else None


def get_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


def get_rules() -> dict[str, Any] | None:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def logical_rules(mesh: Mesh | None, rules: dict[str, Any] | None = DEFAULT_RULES):
    prev = (get_mesh(), get_rules())
    set_rules(mesh, rules)
    try:
        yield
    finally:
        set_rules(*prev)


def _resolve(names: tuple[str | None, ...], rules: dict[str, Any],
             mesh: Mesh) -> P:
    used: set[str] = set()
    out = []
    for nm in names:
        axes = rules.get(nm) if nm else None
        if axes is None:
            out.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        # drop axes missing from the mesh or already used (a mesh axis may
        # shard only one tensor dim), keep the rest
        keep = tuple(a for a in axes if a in mesh.axis_names and a not in used)
        used.update(keep)
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(keep)
    return P(*out)


def logical_spec(names: tuple[str | None, ...]) -> P:
    """Resolve logical names to a PartitionSpec under the active rules."""
    mesh, rules = get_mesh(), get_rules()
    if mesh is None or rules is None:
        return P()
    return _resolve(names, rules, mesh)


def shard(x: jax.Array, *names: str | None) -> jax.Array:
    """Annotate an activation with logical axes (no-op without a mesh).

    Axes whose mesh size does not divide the tensor dim are dropped — an
    uneven constraint (e.g. 40 heads over a 16-way model axis) makes GSPMD
    pad and reshard on every use; measured 70+ GiB/step of collective-permute
    churn on llama4-scout decode before this guard (EXPERIMENTS.md §Perf)."""
    mesh, rules = get_mesh(), get_rules()
    if mesh is None or rules is None:
        return x
    spec = _resolve(tuple(names), rules, mesh)
    fixed = []
    for i, ax in enumerate(spec):
        if ax is None or i >= x.ndim:
            fixed.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        fixed.append(ax if x.shape[i] % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*fixed)))
