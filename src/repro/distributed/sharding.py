"""Logical-axis sharding rules (MaxText-style).

Model code annotates tensors with *logical* axis names; a rules table maps
them to mesh axes per run mode.  The same model definition then runs on a
single CPU device (rules empty → no-op), the 256-chip pod, or the 512-chip
multi-pod mesh without modification.

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


def named_sharding(names: tuple[str | None, ...]) -> NamedSharding | None:
    mesh, rules = get_mesh(), get_rules()
    if mesh is None or rules is None:
        return None
    return NamedSharding(mesh, _resolve(names, rules, mesh))


def tree_shardings(logical_tree: Any) -> Any:
    """Map a pytree of logical-name tuples to NamedShardings (dry-run
    in_shardings).  Leaves are tuples of str/None."""
    mesh, rules = get_mesh(), get_rules()
    assert mesh is not None and rules is not None

    def leaf(names):
        return NamedSharding(mesh, _resolve(tuple(names), rules, mesh))

    return jax.tree.map(leaf, logical_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and
                        all(isinstance(i, (str, type(None))) for i in x))


def shardings_for(abstract_tree: Any, logical_tree: Any) -> Any:
    """Like ``tree_shardings`` but validated against the abstract leaves:
    mesh axes whose size does not divide the tensor dim are dropped for that
    dim (jit ``in_shardings`` requires exact divisibility — e.g. whisper's
    51865 vocab cannot shard 16 ways and falls back to replication)."""
    mesh, rules = get_mesh(), get_rules()
    assert mesh is not None and rules is not None

    def leaf(abs_leaf, names):
        spec = _resolve(tuple(names), rules, mesh)
        fixed = []
        for i, ax in enumerate(spec):
            if ax is None or i >= len(abs_leaf.shape):
                fixed.append(None)
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            fixed.append(ax if abs_leaf.shape[i] % size == 0 else None)
        return NamedSharding(mesh, P(*fixed))

    is_names = lambda x: isinstance(x, tuple) and all(
        isinstance(i, (str, type(None))) for i in x)
    return jax.tree.map(leaf, abstract_tree, logical_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
                        )


def divisible(dim: int, names: tuple[str | None, ...], axis_index: int) -> bool:
    """Check a tensor dim divides the mapped mesh axes (used by configs to
    drop illegal shardings, e.g. 8 kv heads over a 16-way model axis)."""
    mesh, rules = get_mesh(), get_rules()
    if mesh is None or rules is None:
        return True
    spec = _resolve(names, rules, mesh)
    ax = spec[axis_index] if axis_index < len(spec) else None
    if ax is None:
        return True
    axes = (ax,) if isinstance(ax, str) else ax
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return dim % size == 0
