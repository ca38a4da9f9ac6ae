"""DeviceIndex — every device-side array of a built Dumpy index, as one
registered pytree (DESIGN.md §2; DumpyOS-style parallel serving).

``DumpyIndex`` keeps the host artifacts (routing tree, numpy flat layout,
raw collection).  Device state used to be scattered — ad-hoc ``jnp.asarray``
uploads in ``search_device``, window-schedule caches on the index, a
separate one-shot plan in ``core/distributed`` — which made the sharded
search impossible to express.  ``DeviceIndex`` unifies it:

* the ordered collection, tombstone mask and original-id table live in a
  ``[S, Tp, n]`` *leaf-aligned* shard layout: leaves are partitioned into
  ``S`` contiguous groups cut only at leaf boundaries (so every leaf pack
  stays contiguous inside one shard) and each shard is padded to the common
  row count ``Tp``, a multiple of 128 (pad rows: ``alive=False``,
  ``id=-1``, zero series);
* each shard's local-to-global leaf id table and its fixed-size span
  schedule (windows + (leaf, window)-intersection edges) are precomputed
  so each shard can run the windowed-pruning loop locally on the leaf
  bounds of the one replicated envelope table — the same table serves
  both metrics (the interval MINDIST of ``core.metric`` compares it
  against the query PAA for ED and against the query's LB_Keogh envelope
  summary for DTW, so no DTW-specific leaf state is uploaded); the span
  size is a multiple of 128 rows, so every span starts on a 128-row tile
  and the span-scan kernel can copy its rows, and its ids as a lane slice,
  straight from HBM;
* the global leaf table (``leaf_start/size`` in flattened ``S·Tp`` row
  coordinates, global lo/hi envelopes) and the flattened routing tables
  serve the batched approximate descent; the sibling routing tables
  (per-edge/per-node contiguous subtree leaf spans, per-leaf parent group,
  begin-sorted distinct-children member lists) drive the extended-search
  (Alg. 4) root→subtree descent and its lower-bound-ordered leaf schedule;
* ``inv_order`` maps an original id to the flattened row of its first
  replica (fuzzy duplication makes the map one-to-many; the remaining
  replicas are recoverable from ``ids``).

The pytree registration makes a ``DeviceIndex`` a legal jit argument: array
fields are children, everything shape-determining is static aux data, so
searches take the whole index as one argument and retracing only happens
when the layout actually changes.  ``shard(mesh)`` places the ``[S, ...]``
fields with ``NamedSharding(mesh, P("data", None, ...))`` (leaf-aligned
shard boundaries by construction) and replicates the small tables; the
sharded exact search then runs shard-local loops and merges per-shard top-k
with an all-gather (see ``search_device.exact_search_device_batch``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import TYPE_CHECKING

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

if TYPE_CHECKING:  # pragma: no cover - import cycle guard (index.py builds us)
    from .index import DumpyIndex


# Children of the pytree, in flatten order.  ``_SHARDED_FIELDS`` are the
# ``[S, ...]`` arrays placed over the data axis; the rest replicate.
_ARRAY_FIELDS = (
    "db", "alive", "ids", "leaf_gid",
    "win_start", "win_lead", "win_size", "edge_leaf", "edge_win",
    "leaf_start", "leaf_size", "leaf_lo_g", "leaf_hi_g", "inv_order",
    "node_csl", "node_shift", "node_lam",
    "rt_parent", "rt_sid", "rt_leaf", "rt_child", "rt_lo", "rt_hi",
    "rt_nl", "rt_begin", "rt_end",
    "node_begin", "node_end", "leaf_parent",
    "grp_off", "grp_begin", "grp_end", "grp_lo", "grp_hi",
)
_SHARDED_FIELDS = frozenset({
    "db", "alive", "ids", "leaf_gid",
    "win_start", "win_lead", "win_size", "edge_leaf", "edge_win",
})
_META_FIELDS = ("n", "w", "chunk", "depth", "lmax", "total",
                "has_duplicates", "max_replica", "row_bounds",
                "gmax", "leaf_bounds", "shard_health", "mesh")


def data_axes(mesh):
    """The mesh axes the ``[S, ...]`` fields shard over."""
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


@dataclasses.dataclass(frozen=True)
class DeviceIndex:
    # -- sharded over the data axis ([S, ...], leaf-aligned) -----------------
    db: jax.Array          # [S, Tp, n] f32 ordered collection (zero pad)
    alive: jax.Array       # [S, Tp] bool tombstone mask (False pad)
    ids: jax.Array         # [S, Tp] i32 original ids (-1 pad)
    leaf_gid: jax.Array    # [S, Lp] i32 local leaf -> global leaf id (-1 pad)
    win_start: jax.Array   # [S, W] i32 span schedule (clamped starts)
    win_lead: jax.Array    # [S, W] i32 masked prefix of end-clamped spans
    win_size: jax.Array    # [S, W] i32 live rows per span (0 = pad span)
    edge_leaf: jax.Array   # [S, E] i32 (local leaf, span) intersections;
    edge_win: jax.Array    # [S, E] i32 pads point at the +inf pad leaf
    # -- replicated ----------------------------------------------------------
    leaf_start: jax.Array  # [L] i32 leaf start in flattened S*Tp coordinates
    leaf_size: jax.Array   # [L] i32
    leaf_lo_g: jax.Array   # [L, w] f32 global leaf envelopes
    leaf_hi_g: jax.Array   # [L, w] f32
    inv_order: jax.Array   # [N] i32 original id -> first flattened row (-1 dead pad)
    node_csl: jax.Array    # [M, lam_max] i32 routing: chosen segments
    node_shift: jax.Array  # [M, lam_max] i32
    node_lam: jax.Array    # [M] i32
    rt_parent: jax.Array   # [Eg] i32 routing edge list (grouped by parent)
    rt_sid: jax.Array      # [Eg] i32
    rt_leaf: jax.Array     # [Eg] i32
    rt_child: jax.Array    # [Eg] i32
    rt_lo: jax.Array       # [Eg, w] f32 child region bounds
    rt_hi: jax.Array       # [Eg, w] f32
    # sibling routing tables (extended search, Alg. 4)
    rt_nl: jax.Array       # [Eg] i32 #leaves under the edge target
    rt_begin: jax.Array    # [Eg] i32 contiguous leaf span of the target
    rt_end: jax.Array      # [Eg] i32
    node_begin: jax.Array  # [M] i32 per-internal-node subtree leaf span
    node_end: jax.Array    # [M] i32
    leaf_parent: jax.Array  # [L] i32 parent internal node (-1: root leaf)
    grp_off: jax.Array     # [M+1] i32 distinct-children group offsets
    grp_begin: jax.Array   # [G+gmax] i32 member spans, begin-sorted per
    grp_end: jax.Array     # [G+gmax] i32 group; gmax sentinel pad rows so a
    grp_lo: jax.Array      # [G+gmax, w] f32 fixed-width dynamic slice of any
    grp_hi: jax.Array      # [G+gmax, w] f32 group stays in bounds
    # -- static (aux data; part of the jit cache key) ------------------------
    n: int                 # series length
    w: int                 # SAX word length
    chunk: int             # effective span size of the schedule
    depth: int             # routing descent depth
    lmax: int              # max leaf size (approximate-path scan width)
    total: int             # real (unpadded) ordered rows
    has_duplicates: bool   # fuzzy layout -> top-k needs the replica margin
    max_replica: int
    row_bounds: tuple      # S+1 ordered-row cuts (leaf-aligned, host ints)
    gmax: int              # max distinct children of any internal node
    leaf_bounds: tuple     # S+1 leaf-id cuts matching row_bounds
    # ``None`` = all shards healthy (the canonical form — searches lower
    # byte-identically to the pre-degraded programs); a tuple of S bools
    # masks dead shards out of every merge (docs/robustness.md).  Static
    # aux data, not an array: health changes are rare, and keeping it out
    # of the children means the all-healthy jit cache entries never churn.
    shard_health: tuple | None = None
    # the mesh the index is placed on (``shard``), ``None`` on one device:
    # the search programs run their Pallas kernels inside ``shard_map`` over
    # it, since XLA cannot partition a Mosaic kernel across chips
    mesh: object = None

    # -- shapes --------------------------------------------------------------
    @property
    def n_shards(self) -> int:
        return self.db.shape[0]

    @property
    def n_live_shards(self) -> int:
        if self.shard_health is None:
            return self.n_shards
        return sum(bool(h) for h in self.shard_health)

    @property
    def shard_rows(self) -> int:
        return self.db.shape[1]

    @property
    def n_leaves(self) -> int:
        return self.leaf_start.shape[0]

    def replace(self, **kw) -> "DeviceIndex":
        return dataclasses.replace(self, **kw)

    # -- construction --------------------------------------------------------
    @classmethod
    def from_index(cls, index: "DumpyIndex", chunk: int = 2048,
                   n_shards: int = 1, *, db_device=None,
                   mesh=None) -> "DeviceIndex":
        """Build the full device state from a host ``DumpyIndex``.

        ``n_shards`` fixes the leading axis; the shard boundaries are the
        leaf boundaries nearest the ideal ``total/S`` cuts, so a leaf never
        straddles two shards and the span loop needs no cross-shard windows.

        ``db_device`` — optional device-resident ``[total, n]`` array already
        in leaf-contiguous order (the device build's gather output): the data
        plane is then assembled on device and the host ``db_ordered``
        permutation is never materialized.

        ``mesh`` — place the result on it (:meth:`shard`).  The ``[S, Tp,
        n]`` slab goes shard by shard straight to the devices that own each
        shard, so no single device ever holds the whole padded slab.
        """
        flat = index.flat
        offs = np.asarray(flat.leaf_offsets, np.int64)
        L = flat.n_leaves
        total = int(offs[-1])
        n = index.db.shape[1]
        w = flat.leaf_lo.shape[1]
        S = max(int(n_shards), 1)

        # leaf-aligned cuts: the leaf boundary nearest each ideal row split
        cut_leaf = [0]
        for s in range(1, S):
            ideal = s * total / S
            j = int(np.searchsorted(offs, ideal))
            if j > 0 and (j > L or ideal - float(offs[j - 1])
                          < float(offs[j]) - ideal):
                j -= 1
            cut_leaf.append(min(max(j, cut_leaf[-1]), L))
        cut_leaf.append(L)
        row_bounds = tuple(int(offs[c]) for c in cut_leaf)

        Tp = _round_rows(max(row_bounds[s + 1] - row_bounds[s]
                         for s in range(S)))
        chunk_eff = min(_round_rows(chunk), Tp)
        W = math.ceil(Tp / chunk_eff)
        Lp = max(cut_leaf[s + 1] - cut_leaf[s] for s in range(S)) + 1  # +pad

        db_sh = np.zeros((S, Tp, n), np.float32)
        alive_sh = np.zeros((S, Tp), bool)
        ids_sh = np.full((S, Tp), -1, np.int32)
        gid_sh = np.full((S, Lp), -1, np.int32)
        win_start = np.zeros((S, W), np.int32)
        win_lead = np.zeros((S, W), np.int32)
        win_size = np.zeros((S, W), np.int32)
        edges: list[tuple[list, list]] = []

        order = np.asarray(flat.order, np.int64)
        alive_ord = index.alive[order]
        pos_flat = np.empty(total, np.int64)   # ordered row -> flattened row
        for s in range(S):
            r0, r1 = row_bounds[s], row_bounds[s + 1]
            l0, l1 = cut_leaf[s], cut_leaf[s + 1]
            Ts = r1 - r0
            if db_device is None:
                db_sh[s, :Ts] = index.db_ordered[r0:r1]
            alive_sh[s, :Ts] = alive_ord[r0:r1]
            ids_sh[s, :Ts] = order[r0:r1]
            gid_sh[s, :l1 - l0] = np.arange(l0, l1)
            pos_flat[r0:r1] = s * Tp + np.arange(Ts)
            local_offs = offs[l0:l1 + 1] - r0
            el, ew = [], []
            for wi, w0 in enumerate(range(0, Tp, chunk_eff)):
                st = min(w0, max(Tp - chunk_eff, 0))
                size = min(max(Ts - w0, 0), chunk_eff)
                win_start[s, wi] = st
                win_lead[s, wi] = w0 - st
                win_size[s, wi] = size
                if size > 0:
                    la = int(np.searchsorted(local_offs, w0, "right")) - 1
                    lb = int(np.searchsorted(local_offs, w0 + size, "left"))
                    for lid in range(max(la, 0), lb):
                        el.append(lid)
                        ew.append(wi)
            edges.append((el, ew))

        # pad edges aim at the +inf pad leaf / the last span: segment-min
        # treats them as no-ops, and edge_win stays sorted
        E = max(max(len(el) for el, _ in edges), 1)
        edge_leaf = np.full((S, E), Lp - 1, np.int32)
        edge_win = np.full((S, E), W - 1, np.int32)
        for s, (el, ew) in enumerate(edges):
            edge_leaf[s, :len(el)] = el
            edge_win[s, :len(ew)] = ew

        leaf_start = np.zeros(max(L, 1), np.int32)
        for s in range(S):
            l0, l1 = cut_leaf[s], cut_leaf[s + 1]
            leaf_start[l0:l1] = s * Tp + (offs[l0:l1] - row_bounds[s])
        leaf_size = np.diff(offs).astype(np.int32) if L else np.ones(1, np.int32)

        inv = np.full(index.db.shape[0], -1, np.int64)
        inv[order[::-1]] = pos_flat[::-1]       # first replica wins

        rt = index.routing_flat
        gmax = rt.gmax
        # gmax sentinel rows so the schedule's fixed-width dynamic slice of
        # any group stays in bounds: begin/end = i32 max (never matches a
        # leaf id and keeps the begin-sorted order), bounds = +inf (their
        # MINDIST is +inf, and invalid members are masked anyway)
        big = np.iinfo(np.int32).max
        grp_begin = np.concatenate([rt.grp_begin,
                                    np.full(gmax, big, np.int32)])
        grp_end = np.concatenate([rt.grp_end, np.full(gmax, big, np.int32)])
        grp_lo = np.concatenate([rt.grp_lo,
                                 np.full((gmax, w), np.inf, np.float32)])
        grp_hi = np.concatenate([rt.grp_hi,
                                 np.full((gmax, w), np.inf, np.float32)])
        db_sharding = None
        if mesh is not None:
            db_sharding = NamedSharding(mesh, P(data_axes(mesh), None, None))
        if db_device is None:
            db_j = (jnp.asarray(db_sh) if db_sharding is None
                    else jax.device_put(db_sh, db_sharding))
        elif db_sharding is None:
            parts = [_padded_rows(db_device, row_bounds[s], row_bounds[s + 1],
                                  Tp) for s in range(S)]
            db_j = parts[0] if S == 1 else jnp.concatenate(parts)
        else:
            # each device receives only the shard it owns
            per_dev = []
            for d, ix in db_sharding.addressable_devices_indices_map(
                    (S, Tp, n)).items():
                s = ix[0].start or 0
                per_dev.append(jax.device_put(
                    _padded_rows(db_device, row_bounds[s],
                                 row_bounds[s + 1], Tp), d))
            db_j = jax.make_array_from_single_device_arrays(
                (S, Tp, n), db_sharding, per_dev)
        dev = cls(
            db=db_j, alive=jnp.asarray(alive_sh),
            ids=jnp.asarray(ids_sh),
            leaf_gid=jnp.asarray(gid_sh),
            win_start=jnp.asarray(win_start), win_lead=jnp.asarray(win_lead),
            win_size=jnp.asarray(win_size),
            edge_leaf=jnp.asarray(edge_leaf), edge_win=jnp.asarray(edge_win),
            leaf_start=jnp.asarray(leaf_start), leaf_size=jnp.asarray(leaf_size),
            leaf_lo_g=jnp.asarray(flat.leaf_lo), leaf_hi_g=jnp.asarray(flat.leaf_hi),
            inv_order=jnp.asarray(inv.astype(np.int32)),
            node_csl=jnp.asarray(rt.node_csl), node_shift=jnp.asarray(rt.node_shift),
            node_lam=jnp.asarray(rt.node_lam),
            rt_parent=jnp.asarray(rt.edge_parent),
            rt_sid=jnp.asarray(rt.edge_sid.astype(np.int32)),
            rt_leaf=jnp.asarray(rt.edge_leaf), rt_child=jnp.asarray(rt.edge_child),
            rt_lo=jnp.asarray(rt.edge_lo), rt_hi=jnp.asarray(rt.edge_hi),
            rt_nl=jnp.asarray(rt.edge_nl), rt_begin=jnp.asarray(rt.edge_begin),
            rt_end=jnp.asarray(rt.edge_end),
            node_begin=jnp.asarray(rt.node_begin),
            node_end=jnp.asarray(rt.node_end),
            leaf_parent=jnp.asarray(rt.leaf_parent),
            grp_off=jnp.asarray(rt.grp_off),
            grp_begin=jnp.asarray(grp_begin), grp_end=jnp.asarray(grp_end),
            grp_lo=jnp.asarray(grp_lo), grp_hi=jnp.asarray(grp_hi),
            n=n, w=w, chunk=chunk_eff, depth=rt.depth,
            lmax=max(int(np.diff(offs).max()) if L else 1, 1),
            total=total,
            has_duplicates=index.stats.n_duplicates > 0,
            max_replica=int(index.params.max_replica),
            row_bounds=row_bounds,
            gmax=gmax,
            leaf_bounds=tuple(int(c) for c in cut_leaf),
        )
        return dev.shard(mesh) if mesh is not None else dev

    # -- sharding ------------------------------------------------------------
    def shardings(self, mesh) -> "DeviceIndex":
        """A DeviceIndex-shaped pytree of NamedShardings: the ``[S, ...]``
        fields split over the mesh's :func:`data_axes` on dim 0, everything
        else replicated.  Usable both for ``device_put`` and as jit
        ``in_shardings``."""
        axes = data_axes(mesh)
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        repl = NamedSharding(mesh, P())
        kw = {}
        for f in _ARRAY_FIELDS:
            leaf = getattr(self, f)
            if f in _SHARDED_FIELDS:
                kw[f] = NamedSharding(
                    mesh, P(axes_t, *([None] * (len(leaf.shape) - 1))))
            else:
                kw[f] = repl
        return dataclasses.replace(self, **kw)

    def shard(self, mesh) -> "DeviceIndex":
        """Place the index on ``mesh``: shards over the data axes (leaf
        aligned by construction), small tables replicated."""
        placed = jax.device_put(self, self.shardings(mesh))
        return dataclasses.replace(placed, mesh=mesh)

    # -- incremental state ---------------------------------------------------
    def with_shard_health(self, health) -> "DeviceIndex":
        """Mark shards dead/alive for degraded-mode search.  ``health`` is a
        length-``n_shards`` boolean sequence (or ``None`` to clear); all-True
        canonicalizes to ``None`` so the healthy index is a single static
        state and healthy searches reuse their existing compiled programs."""
        if health is None:
            return dataclasses.replace(self, shard_health=None)
        health = tuple(bool(h) for h in health)
        if len(health) != self.n_shards:
            raise ValueError(
                f"shard_health has {len(health)} entries for "
                f"{self.n_shards} shards")
        if not any(health):
            raise ValueError("shard_health marks every shard dead — "
                             "no data left to search")
        if all(health):
            health = None
        return dataclasses.replace(self, shard_health=health)

    def with_alive(self, alive_by_id: np.ndarray) -> "DeviceIndex":
        """Re-derive the padded tombstone mask from the host per-id ``alive``
        vector (deletions/undeletions without rebuilding the layout).  Every
        fuzzy replica of a dead id dies with it."""
        ids_np = np.asarray(self.ids)
        new = np.zeros(ids_np.shape, bool)
        m = ids_np >= 0
        new[m] = np.asarray(alive_by_id, bool)[ids_np[m]]
        arr = jnp.asarray(new)
        sharding = getattr(self.alive, "sharding", None)
        if sharding is not None:
            arr = jax.device_put(arr, sharding)
        return dataclasses.replace(self, alive=arr)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _padded_rows(db: jax.Array, r0: int, r1: int, Tp: int) -> jax.Array:
    """Rows ``[r0, r1)`` of a ``[total, n]`` device array as one zero-padded
    ``[1, Tp, n]`` shard — one program, so the only new buffer is the shard
    itself (eager slice + pad + reshape would each copy it)."""
    return jnp.pad(db[r0:r1], ((0, Tp - (r1 - r0)), (0, 0)))[None]


def _flatten(dev: DeviceIndex):
    return (tuple(getattr(dev, f) for f in _ARRAY_FIELDS),
            tuple(getattr(dev, f) for f in _META_FIELDS))


def _unflatten(aux, children) -> DeviceIndex:
    return DeviceIndex(**dict(zip(_ARRAY_FIELDS, children)),
                       **dict(zip(_META_FIELDS, aux)))


jax.tree_util.register_pytree_node(DeviceIndex, _flatten, _unflatten)


def _round_rows(rows: int) -> int:
    """``rows`` rounded up to a whole 128-row tile (at least one): shard
    and span sizes, so that every span starts on a tile, where the span
    scan's copy of the span's ids (a lane slice) can start."""
    return max(-(-int(rows) // 128) * 128, 128)


def abstract_device_index(n_series: int, length: int, w: int, *,
                          n_shards: int = 1, chunk: int = 4096,
                          n_leaves: int = 4096, lam_max: int = 4,
                          depth: int = 8, gmax: int = 64,
                          shard_health: tuple | None = None,
                          mesh=None) -> DeviceIndex:
    """A ShapeDtypeStruct-leaved DeviceIndex for lower/compile dry-runs:
    equal-sized leaves, evenly divided shards (no data, shapes only).
    ``mesh`` marks it as placed there, as :meth:`DeviceIndex.shard` does."""
    S = max(int(n_shards), 1)
    Ts = math.ceil(n_series / S)
    Tp = _round_rows(Ts)
    Ls = math.ceil(n_leaves / S)
    Lp = Ls + 1
    chunk_eff = min(_round_rows(chunk), Tp)
    W = math.ceil(Tp / chunk_eff)
    E = Ls + W
    M = max(n_leaves // 4, 1)
    Eg = max(n_leaves, 1)
    G = Eg + gmax
    f32, i32, b8 = jnp.float32, jnp.int32, jnp.bool_
    sds = jax.ShapeDtypeStruct
    return DeviceIndex(
        db=sds((S, Tp, length), f32), alive=sds((S, Tp), b8),
        ids=sds((S, Tp), i32),
        leaf_gid=sds((S, Lp), i32),
        win_start=sds((S, W), i32), win_lead=sds((S, W), i32),
        win_size=sds((S, W), i32),
        edge_leaf=sds((S, E), i32), edge_win=sds((S, E), i32),
        leaf_start=sds((n_leaves,), i32), leaf_size=sds((n_leaves,), i32),
        leaf_lo_g=sds((n_leaves, w), f32), leaf_hi_g=sds((n_leaves, w), f32),
        inv_order=sds((n_series,), i32),
        node_csl=sds((M, lam_max), i32), node_shift=sds((M, lam_max), i32),
        node_lam=sds((M,), i32),
        rt_parent=sds((Eg,), i32), rt_sid=sds((Eg,), i32),
        rt_leaf=sds((Eg,), i32), rt_child=sds((Eg,), i32),
        rt_lo=sds((Eg, w), f32), rt_hi=sds((Eg, w), f32),
        rt_nl=sds((Eg,), i32), rt_begin=sds((Eg,), i32),
        rt_end=sds((Eg,), i32),
        node_begin=sds((M,), i32), node_end=sds((M,), i32),
        leaf_parent=sds((n_leaves,), i32),
        grp_off=sds((M + 1,), i32),
        grp_begin=sds((G,), i32), grp_end=sds((G,), i32),
        grp_lo=sds((G, w), f32), grp_hi=sds((G, w), f32),
        n=length, w=w, chunk=chunk_eff, depth=depth,
        lmax=max(math.ceil(n_series / max(n_leaves, 1)), 1), total=n_series,
        has_duplicates=False, max_replica=3,
        row_bounds=tuple(min(s * Ts, n_series) for s in range(S + 1)),
        gmax=gmax,
        leaf_bounds=tuple(min(s * Ls, n_leaves) for s in range(S + 1)),
        shard_health=shard_health, mesh=mesh,
    )
