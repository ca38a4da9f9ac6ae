"""Device backend for index construction (Coconut-style bottom-up build).

Instead of the host backend's per-row tree recursion, the collection is
reduced to its *distinct SAX words* up front with one device lexsort, and the
adaptive split (Algorithm 2) then runs over grouped ``(word, multiplicity)``
pairs — the tree is built over at most ``U ≤ N`` word groups, and the final
leaf-contiguous permutation is produced by a single device sort keyed on each
row's leaf atom.  The five build stages (``core/build.py`` module docstring)
map as:

  1. encode       — ``sax_encode_np`` (default, bitwise-identical to the host
                    backend) or the ``jnp`` / Pallas device encoders
  2. group        — :func:`_lexsort_words`: on-device lexsort of packed SAX
                    words → (permutation, group boundaries, row → word map)
  3. split plan   — ``plan_node_grouped`` (shared with the host layer):
                    weighted histograms / variances over word groups feed the
                    vectorized Alg. 2 evaluator ``split.plan_split``
  4. pack         — ``pack_siblings`` (shared with the host backend verbatim)
  5. materialize  — one device ``lexsort`` by (leaf-atom rank, row id) emits
                    the leaf-contiguous order; ``db_ordered`` is a device
                    gather, never round-tripped through the host.  On a
                    mesh the collection never goes whole to one device:
                    each shard's rows, cut where ``DeviceIndex`` cuts them,
                    are gathered on the host and copied to the device that
                    owns the shard (``device_index.place_shards``)

The result layout equals the host build's up to the tie-breaking documented
in ``docs/build_pipeline.md``: leaf membership, leaf order, CSR offsets and
routing tables match exactly on every dataset where no two split plans score
exactly equal (property-tested in ``tests/test_build_pipeline.py``).  Both
drivers expand breadth-first so the fuzzy replica budget (§6) is consumed in
the same node order.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import fuzzy as fuzzy_mod
from .build import (BuildStats, DumpyParams, TreeNode, children_isax,
                    collect_leaves, finalize_stats, pack_siblings,
                    partition_by_sid, plan_node_grouped)
from .device_index import place_shards
from .index import FlatLeaves, flatten_tree
from .lb import node_bounds_np
from .sax import next_bits_np, pack_bits_np, sax_encode_jnp, sax_encode_np


#: rows the host encoder takes at a time: bounds its float64 staging
ENCODE_ROWS = 1 << 16


@dataclasses.dataclass
class DeviceBuildResult:
    """Everything ``DumpyIndex`` needs, plus the device-resident ordered
    collection so ``DeviceIndex`` can be assembled without a host copy:
    ``db_ordered_dev`` on one device, or ``db_shards`` on a mesh."""
    root: TreeNode
    stats: BuildStats
    paa: np.ndarray            # [N, w] float32
    sax: np.ndarray            # [N, w] uint8
    flat: FlatLeaves
    order: np.ndarray          # [total] int64 (= flat.order)
    db_ordered_dev: jax.Array | None  # [total, n] float32, on device
    db_shards: dict | None            # mesh: {device: [1, Tp, n] float32}


def _encode_np(db: np.ndarray, p) -> tuple[np.ndarray, np.ndarray]:
    """``sax_encode_np`` of ``db`` in blocks of :data:`ENCODE_ROWS` rows:
    each row's summary is computed alone, so the result is bitwise the
    whole call's, without a float64 copy of the whole collection."""
    paa = np.empty((db.shape[0], p.w), np.float32)
    sax = np.empty((db.shape[0], p.w), np.uint8)
    for r in range(0, db.shape[0], ENCODE_ROWS):
        paa[r:r + ENCODE_ROWS], sax[r:r + ENCODE_ROWS] = sax_encode_np(
            db[r:r + ENCODE_ROWS], p)
    return paa, sax


@functools.partial(jax.jit, static_argnames=("w", "b"))
def _lexsort_words(sax: jax.Array, w: int, b: int):
    """Stage 2: sort rows by SAX word and delimit equal-word groups.

    Packs ``32 // b`` symbols per uint32 key column (x64 is disabled) and
    lexsorts with an explicit row-id key as the least-significant tiebreak,
    so equal words keep ascending row order without relying on sort
    stability.  Returns ``(perm, new_group_flags, row → word index)``.
    """
    n = sax.shape[0]
    per = 32 // b
    sax32 = sax.astype(jnp.uint32)
    cols = []
    for c in range(0, w, per):
        seg = sax32[:, c:min(c + per, w)]
        key = jnp.zeros(n, jnp.uint32)
        for j in range(seg.shape[1]):
            key = (key << b) | seg[:, j]
        cols.append(key)
    # jnp.lexsort: last key is primary → (row id, least-sig col, ..., col 0)
    perm = jnp.lexsort(tuple([jnp.arange(n, dtype=jnp.int32)]
                             + cols[::-1]))
    srt = sax32[perm]
    flags = jnp.concatenate([jnp.ones(1, bool),
                             jnp.any(srt[1:] != srt[:-1], axis=1)])
    winv = (jnp.cumsum(flags) - 1).astype(jnp.int32)
    row2word = jnp.zeros(n, jnp.int32).at[perm].set(winv)
    return perm, flags, row2word


def device_build(db: np.ndarray, params: DumpyParams | None = None, *,
                 encoder: str = "np",
                 precomputed: tuple[np.ndarray, np.ndarray] | None = None,
                 mesh=None) -> DeviceBuildResult:
    """Bottom-up build over grouped SAX words (Algorithm 1 on the device).

    ``encoder`` — ``"np"`` (default; bitwise-identical summaries to the host
    backend, required for exact layout parity), ``"jnp"`` or ``"pallas"``
    (device PAA in float32 — borderline symbols may differ from the host
    encoder by one breakpoint, see docs/build_pipeline.md).

    ``mesh`` — lay the ordered rows out as the shards of a ``DeviceIndex``
    on it (``db_shards``): the collection never goes whole to one device,
    so only the host encoder applies.  The layout itself is the same.

    Stage 1 is the span ``dumpy.build.encode``, stages 2–4 (with fuzzy
    duplication) ``dumpy.build.split``, stage 5 ``dumpy.build.layout``,
    which ends once the ordered rows are on the device.
    """
    p = params or DumpyParams()
    db = np.ascontiguousarray(db, np.float32)
    n = db.shape[0]
    w, b = p.sax.w, p.sax.b
    p.sax.validate_series_length(db.shape[-1])
    if mesh is not None and encoder != "np" and precomputed is None:
        raise ValueError(f"encoder {encoder!r} needs the collection on one "
                         "device; a mesh build encodes on the host")
    db_dev = jnp.asarray(db) if mesh is None else None

    # -- Stage 1: encode ----------------------------------------------------
    with obs.span("dumpy.build.encode"):
        if precomputed is not None:
            paa, sax = precomputed
        elif encoder == "np":
            paa, sax = _encode_np(db, p.sax)
        elif encoder == "jnp":
            paa_j, sax_j = sax_encode_jnp(db_dev, w, b)
            paa = np.asarray(paa_j, np.float32)
            sax = np.asarray(sax_j).astype(np.uint8)
        elif encoder == "pallas":
            from ..kernels import ops
            paa_j, sax_j = ops.sax_encode(db_dev, w, b)
            paa = np.asarray(paa_j, np.float32)
            sax = np.asarray(sax_j).astype(np.uint8)
        else:
            raise ValueError(f"unknown encoder: {encoder!r}")

    stats = BuildStats(n_series=n)
    root = TreeNode(np.zeros(w, np.int64), np.zeros(w, np.int64), depth=0)
    root.size = n
    if n <= p.th:                          # trivial collection: root is a leaf
        root.series_ids = np.arange(n, dtype=np.int64)
        finalize_stats(root, stats, p.th)
        flat = flatten_tree(root, b)
        shards = (None if mesh is None else
                  place_shards(db, flat.order, flat.leaf_offsets, mesh))
        return DeviceBuildResult(root, stats, paa, sax, flat,
                                 flat.order, db_dev, shards)

    # -- Stage 2: group by SAX word ----------------------------------------
    with obs.span("dumpy.build.split"):
        perm_d, flags_d, row2word_d = _lexsort_words(jnp.asarray(sax), w, b)
        perm = np.asarray(perm_d, np.int64)
        flags = np.asarray(flags_d)
        starts = np.flatnonzero(flags)
        woff = starts.astype(np.int64)                  # word → offset into perm
        wcount = np.diff(np.append(starts, n)).astype(np.int64)
        words = sax[perm[starts]].astype(np.int64)      # [U, w] distinct words
        row2word = np.asarray(row2word_d, np.int64)
        U = len(words)

        rep_budget = np.full(n, p.max_replica, np.int32)
        # per-leaf *atoms*: ordered (word-group selection, extra rows) payloads —
        # the unit the materialization stage lays out contiguously
        leaf_atoms: dict[int, list[tuple[np.ndarray, np.ndarray]]] = {}
        no_rows = np.empty(0, np.int64)

        def split_word_node(node: TreeNode, wsel: np.ndarray, extras: np.ndarray,
                            is_root: bool):
            avail = [j for j in range(w) if node.card[j] < b]
            if not avail:                       # cannot refine → forced leaf
                leaf_atoms[id(node)] = [(wsel, extras)]
                return []

            # -- Stage 3: adaptive split plan over grouped words ---------------
            if is_root:
                csl = tuple(range(w)) if len(avail) == w else tuple(avail)
            else:
                if len(extras):
                    pw = np.concatenate([words[wsel],
                                         sax[extras].astype(np.int64)])
                    pc = np.concatenate([wcount[wsel],
                                         np.ones(len(extras), np.int64)])
                else:
                    pw, pc = words[wsel], wcount[wsel]
                csl, nev = plan_node_grouped(pw, pc, node.card, avail,
                                             int(pc.sum()), p.split, b)
                stats.plans_evaluated += nev
            node.csl = csl
            cl = list(csl)

            wsids = pack_bits_np(next_bits_np(words[wsel][:, cl],
                                              node.card[cl], b))
            wgroups = partition_by_sid(wsids)           # sid → idx into wsel
            if len(extras):
                esids = pack_bits_np(next_bits_np(sax[extras][:, cl].astype(np.int64),
                                                  node.card[cl], b))
                egroups = partition_by_sid(esids)
            else:
                esids = no_rows
                egroups = {}
            keys = sorted(set(wgroups) | set(egroups))

            # -- fuzzy duplication (§6): same row order as the host driver -----
            dup_extras: dict[int, list[np.ndarray]] = {}
            if p.fuzzy_f > 0.0:
                lens = wcount[wsel]
                offs = np.cumsum(lens) - lens
                pos = (np.arange(int(lens.sum())) - np.repeat(offs, lens)
                       + np.repeat(woff[wsel], lens))
                naturals = np.sort(perm[pos])
                sids_nat = wsids[np.searchsorted(wsel, row2word[naturals])]
                if len(extras):
                    member_rows = np.concatenate([naturals, extras])
                    member_sids = np.concatenate([sids_nat, esids])
                else:
                    member_rows, member_sids = naturals, sids_nat
                dups = fuzzy_mod.fuzzy_duplicates(
                    paa[member_rows], member_sids, node.sym, node.card, csl, b,
                    p.fuzzy_f, set(keys), rep_budget, member_rows)
                for tgt, local_idx in dups:
                    dup_extras.setdefault(tgt, []).append(member_rows[local_idx])
                    stats.n_duplicates += len(local_idx)

            syms, cards = children_isax(node.sym, node.card, csl,
                                        np.asarray(keys, np.int64))
            pending, pending_ids = [], set()
            for k, sid in enumerate(keys):
                g = wgroups.get(sid)
                cw = wsel[g] if g is not None else no_rows
                ce_parts = []
                eg = egroups.get(sid)
                if eg is not None:
                    ce_parts.append(extras[eg])
                ce_parts.extend(dup_extras.get(sid, []))
                ce = np.concatenate(ce_parts) if ce_parts else no_rows
                child = TreeNode(syms[k], cards[k], node.depth + 1)
                child.size = int(wcount[cw].sum()) + len(ce)
                node.children[sid] = child
                if child.size > p.th and bool((cards[k] < b).any()):
                    pending.append((child, cw, ce, False))
                    pending_ids.add(id(child))
                else:
                    leaf_atoms[id(child)] = [(cw, ce)]

            # -- Stage 4: pack small siblings (shared with the host) -----------
            for pnode, _, member_children in pack_siblings(node, p, pending_ids):
                atoms: list[tuple[np.ndarray, np.ndarray]] = []
                for c in member_children:
                    atoms.extend(leaf_atoms.pop(id(c)))
                leaf_atoms[id(pnode)] = atoms
            return pending

        frontier = [(root, np.arange(U, dtype=np.int64), no_rows, True)]
        while frontier:
            nxt = []
            for nd, wsel, extras, rt in frontier:
                nxt.extend(split_word_node(nd, wsel, extras, rt))
            frontier = nxt

    # -- Stage 5: materialize the leaf-contiguous layout --------------------
    with obs.span("dumpy.build.layout"):
        leaves = collect_leaves(root)
        L = len(leaves)
        atom_rank_of_word = np.zeros(U, np.int64)
        atoms_flat: list[tuple[np.ndarray, np.ndarray]] = []
        leaf_sizes = np.zeros(L, np.int64)
        has_extras = False
        for i, leaf in enumerate(leaves):
            leaf.leaf_id = i
            for ws, ex in leaf_atoms[id(leaf)]:
                atom_rank_of_word[ws] = len(atoms_flat)
                atoms_flat.append((ws, ex))
                leaf_sizes[i] += int(wcount[ws].sum()) + len(ex)
                if len(ex):
                    has_extras = True

        # natural rows sorted by (leaf-atom rank, row id): one device lexsort
        rank_rows = jnp.take(jnp.asarray(atom_rank_of_word, dtype=jnp.int32),
                             row2word_d)
        order_nat_d = jnp.lexsort((jnp.arange(n, dtype=jnp.int32), rank_rows))
        if not has_extras:
            order_dev = order_nat_d
            order = np.asarray(order_dev, np.int64)
        else:
            # splice each atom's extra rows behind its natural block on the host
            # (extras exist only under fuzzy duplication), then re-upload
            order_nat = np.asarray(order_nat_d, np.int64)
            parts = []
            off = 0
            for ws, ex in atoms_flat:
                cnt = int(wcount[ws].sum())
                parts.append(order_nat[off:off + cnt])
                off += cnt
                if len(ex):
                    parts.append(ex)
            order = (np.concatenate(parts) if parts else no_rows)
            order_dev = jnp.asarray(order, dtype=jnp.int32)
        db_ordered_dev = shards = None
        if mesh is None:
            db_ordered_dev = jnp.take(db_dev, order_dev, axis=0)

        sym = np.zeros((L, w), np.int16)
        card = np.zeros((L, w), np.uint8)
        for i, leaf in enumerate(leaves):
            sym[i] = leaf.sym
            card[i] = leaf.card
        offsets = np.zeros(L + 1, np.int64)
        np.cumsum(leaf_sizes, out=offsets[1:])
        lo, hi = node_bounds_np(sym, card, b)
        flat = FlatLeaves(sym, card, lo, hi, offsets, order)
        for i, leaf in enumerate(leaves):       # tree stays update/save-capable
            leaf.series_ids = order[offsets[i]:offsets[i + 1]].copy()

        finalize_stats(root, stats, p.th)
        if mesh is None:
            jax.block_until_ready(db_ordered_dev)
        else:
            shards = place_shards(db, order, offsets, mesh)
    return DeviceBuildResult(root, stats, paa, sax, flat, order,
                             db_ordered_dev, shards)
