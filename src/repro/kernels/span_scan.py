"""Pallas TPU kernel: the exact search's span walk, one kernel a call.

The exact ED program orders a shard's fixed-size spans by their lower
bound and walks them until no query can improve (``core/search_device.py``,
``_exact_knn_sharded``).  This kernel is that walk.  Grid ``(S, W)``: the
shards form a parallel axis and the ``W`` spans of each shard's walk order
a sequential one, so the running top-k stays resident in the output blocks
from the first span to the last.

* **Slab streaming.**  ``db`` stays in HBM.  The walk-ordered span starts
  arrive by scalar prefetch; span i+1's ``[chunk, n]`` rows, and its
  ``chunk`` ids from the ``[S, 1, Tp]`` id row, are copied into the second
  of two VMEM buffers while span i is scored.  Span starts lie on 128-row
  tiles (``DeviceIndex``), as the ids' lane slice needs.  The copy of
  span i+1 is issued only if some query's suffix bound there lies below its
  running ``kk``-th best before span i merges, a condition that merging can
  only falsify; once the walk's condition fails, no copy is issued and the
  remaining grid steps do nothing.
* **Distance.**  ``max(|q|² + |x|² − 2 q·x, 0)`` as ``ed2_batch_jnp``: the
  product at ``Precision.HIGHEST``, ``|x|²`` summed from the VMEM slab.
* **Masking.**  A row scores if it is alive (its copied id is not ``-1``)
  and lies inside the span's live window, ``lead <= col < lead + size`` by
  scalar prefetch; a query scores if its span bound lies below its running
  ``kk``-th best.
  Everything else is ``+inf``.
* **Merge.**  ``m = min(max_q #{key < cut}, kk)`` insertion rounds, the
  rounds of ``ops.topk_merge_cutoff``: each takes every query's least
  remaining candidate (lowest column on ties) and inserts it after the held
  entries it does not lie below.  ``m <= kk``, so no span needs a sort, and
  the result is bitwise ``ops.topk_merge`` of the same distances.  The
  rounds read a lane fold of the span's candidates, not the ``[Q, chunk]``
  block: per query and lane the least key below the cut over the lane's
  128-column tiles, its column and how many of the lane's keys lie below
  the cut.  A round takes the least (key, column) of the fold and pops
  it; the fold is rebuilt from the block, past each query's last taken
  entry, only after a query takes an entry whose lane held another
  candidate.  The kernel holds each entry's row in its shard; the
  wrapper maps rows to ids.
* **Termination.**  The loop's own condition, ``any(suffix[:, i] <
  topd[:, kk-1])``, read at every step.

Outputs are the loop's: ``topd/topi [S, Q, kk]``, ``vis [S, Q]`` (spans a
query was active at) and ``work [S, 6]`` (``WORK_KEYS`` order: spans
walked, their live rows, live rows × active queries, spans at which a
candidate entered, merge rounds, merged spans at which some query held two
candidates in one lane).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_I32_MAX = 0x7FFFFFFF
#: most elements of one ``[Q, chunk]`` score block; a larger batch is
#: walked in query groups (each query's merges do not depend on the group)
MAX_BLOCK = 1 << 19


def _key(x: jax.Array) -> jax.Array:
    """f32 → i32 key in ``lax.top_k``'s total order (``ops._order_key``)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & _I32_MAX)


def _unkey(k: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(k ^ ((k >> 31) & _I32_MAX),
                                        jnp.float32)


def _column(blk: jax.Array, c, fill) -> jax.Array:
    """Lane ``c`` of a ``[Q, 128]`` block as a ``[Q, 1]`` column."""
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    return jnp.min(jnp.where(lane == c, blk, fill), axis=1, keepdims=True)


def _kernel(start_ref, lead_ref, size_ref, q_ref, qn_ref, lb_ref, sf_ref,
            nx_ref, id_ref, db_ref, topd_ref, topi_ref, vis_ref, work_ref,
            buf, sem, idb, id_sem, key_ref, fold_ref, st_ref, *, kk: int,
            chunk: int, W: int):
    s, i = pl.program_id(0), pl.program_id(1)
    Q, KP = topd_ref.shape
    C = chunk
    slot = i % 2

    def copies(span, into):
        """The span's rows and its ids, one DMA each."""
        r0 = pl.multiple_of(start_ref[s * W + span], 128)
        return (pltpu.make_async_copy(db_ref.at[s, pl.ds(r0, C), :],
                                      buf.at[into], sem.at[into]),
                pltpu.make_async_copy(id_ref.at[s, :, pl.ds(r0, C)],
                                      idb.at[into], id_sem.at[into]))

    def start(span, into):
        for c in copies(span, into):
            c.start()

    def wait(span, into):
        for c in copies(span, into):
            c.wait()

    @pl.when(i == 0)
    def _init():
        topd_ref[...] = jnp.full((Q, KP), jnp.inf, jnp.float32)
        topi_ref[...] = jnp.full((Q, KP), -1, jnp.int32)
        vis_ref[...] = jnp.zeros((Q, 1), jnp.int32)
        for c in range(8):                 # pending copies 0-1, counters 2-7
            st_ref[c] = 0

    cut = topd_ref[:, kk - 1:kk]                                 # [Q, 1]
    c = i % 128
    lb = _column(lb_ref[...], c, jnp.inf)
    go = jnp.any(_column(sf_ref[...], c, jnp.inf) < cut)
    nxt = jnp.any(_column(nx_ref[...], c, jnp.inf) < cut)

    @pl.when(go)
    def _walk():
        @pl.when(st_ref[slot] == 0)
        def _first():
            start(i, slot)

        @pl.when(nxt)
        def _prefetch():
            start(i + 1, 1 - slot)
            st_ref[1 - slot] = 1

        wait(i, slot)
        st_ref[slot] = 0

        slab = buf[slot]                                         # [C, n]
        # queries on sublanes, rows on lanes: [Q, C] scores, lane-dense
        # (on a v5e 5.5 µs a span against 7.5 with the slab as the left
        # operand and the product transposed; PERF.md §6)
        cross = jax.lax.dot_general(
            q_ref[...], slab, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)                  # [Q, C]
        xn = jnp.sum(slab * slab, axis=1)[None, :]               # [1, C]
        d2 = jnp.maximum((qn_ref[...] + xn) - 2.0 * cross, 0.0)
        r0 = start_ref[s * W + i]
        lead, size = lead_ref[s * W + i], size_ref[s * W + i]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        ids = jnp.where((lane >= lead) & (lane < lead + size), idb[slot],
                        -1)                                      # [1, C]
        qact = lb < cut                                          # [Q, 1]
        dk = _key(jnp.where((ids >= 0) & qact, d2, jnp.inf))
        cutk = _key(cut)
        key_ref[...] = dk
        cnt = jnp.sum((dk < cutk).astype(jnp.int32), axis=1, keepdims=True)
        m = jnp.minimum(jnp.max(cnt), kk)
        l128 = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        slot_i = jax.lax.broadcasted_iota(jnp.int32, (Q, KP), 1)

        def fold(last=None):
            """Fold the block's keys below the cut, past each query's last
            taken ``(key, column)`` where ``last`` is given, onto 128 lanes:
            the least key of each lane (the lowest tile on ties), its
            column and the lane's count."""
            f = jnp.full((Q, 128), _I32_MAX, jnp.int32)
            g = jnp.full((Q, 128), C, jnp.int32)
            n_ = jnp.zeros((Q, 128), jnp.int32)
            for t in range(C // 128):
                kt = key_ref[:, t * 128:(t + 1) * 128]
                ct = l128 + t * 128
                ok = kt < cutk
                if last is not None:
                    lk, lc = last
                    ok &= (kt > lk) | ((kt == lk) & (ct > lc))
                lt = ok & (kt < f)
                f = jnp.where(lt, kt, f)
                g = jnp.where(lt, ct, g)
                n_ += ok.astype(jnp.int32)
            fold_ref[0], fold_ref[1], fold_ref[2] = f, g, n_
            return n_

        def round_(refold, _, carry):
            f, g = fold_ref[0], fold_ref[1]
            vk = jnp.min(f, axis=1, keepdims=True)               # [Q, 1]
            j = jnp.min(jnp.where(f == vk, g, C), axis=1, keepdims=True)
            hit = g == j
            td, ti = topd_ref[...], topi_ref[...]
            tk = _key(td)
            # the held entries the candidate does not lie below: a prefix
            # of the kk slots, since they are held in key order.  A query
            # whose candidate does not enter it inserts past the kk-th
            # slot, which the outputs drop
            le = (tk <= vk) & (slot_i < kk)
            at = jnp.logical_not(le) & (
                (slot_i == 0) | (pltpu.roll(le.astype(jnp.int32), 1, 1) > 0))

            def ins(t, x):
                return jnp.where(le, t,
                                 jnp.where(at, x, pltpu.roll(t, 1, 1)))

            topd_ref[...] = ins(td, _unkey(vk))
            topi_ref[...] = ins(ti, j + r0)
            # pop the entry: a lane that held another candidate hides it
            # until the fold is rebuilt past the entry.  A query whose
            # entry did not enter takes nothing more in this span (its
            # next candidates lie no lower), so only takers call for that
            fold_ref[0] = jnp.where(hit, _I32_MAX, f)
            if refold:
                take = vk < tk[:, kk - 1:kk]

                @pl.when(jnp.any(take & hit & (fold_ref[2] > 1)))
                def _refold():
                    fold((vk, j))

            return carry

        @pl.when(m > 0)
        def _merge():
            collided = jnp.max(fold()) > 1
            st_ref[7] += collided.astype(jnp.int32)

            # with one candidate a lane at most, no round can refold
            @pl.when(collided)
            def _rounds_refold():
                jax.lax.fori_loop(0, m, functools.partial(round_, True), 0)

            @pl.when(jnp.logical_not(collided))
            def _rounds():
                jax.lax.fori_loop(0, m, functools.partial(round_, False), 0)

        act = qact.astype(jnp.int32)
        vis_ref[...] += act
        st_ref[2] += 1
        st_ref[3] += size
        st_ref[4] += size * jnp.sum(act)
        st_ref[5] += (m > 0).astype(jnp.int32)
        st_ref[6] += m

    @pl.when(jnp.logical_not(go) & (st_ref[slot] == 1))
    def _drain():
        wait(i, slot)
        st_ref[slot] = 0

    @pl.when(i == W - 1)
    def _out():
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
        w = jnp.zeros((8, 128), jnp.int32)
        for c in range(6):
            w = jnp.where(lane == c, st_ref[2 + c], w)
        work_ref[...] = w


def _blocks(t: jax.Array, nb: int) -> jax.Array:
    """``[S, Q, W']`` → ``[S, nb, Q, 128]``, padded with ``+inf``."""
    S, Q, Wt = t.shape
    t = jnp.pad(t, ((0, 0), (0, 0), (0, nb * 128 - Wt)),
                constant_values=jnp.inf)
    return t.reshape(S, Q, nb, 128).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("kk", "chunk", "interpret"))
def span_scan(qs: jax.Array, db: jax.Array, alive: jax.Array,
              ids: jax.Array, start: jax.Array, lead: jax.Array,
              size: jax.Array, win_lb: jax.Array, suffix: jax.Array, *,
              kk: int, chunk: int, interpret: bool = False
              ) -> tuple[jax.Array, ...]:
    """Walk every shard's span schedule, in walk order, for ``qs [Q, n]``.

    ``db [S, Tp, n]``, ``alive/ids [S, Tp]``; ``start/lead/size [S, W]``
    the walk-ordered schedule (``Tp``, ``chunk`` and the starts multiples
    of 128 rows, as ``DeviceIndex`` lays them out); ``win_lb [S, Q,
    W]`` the span bounds in walk order and ``suffix [S, Q, W+1]`` their
    suffix minima with a ``+inf`` column appended.  Returns ``(topd [S, Q,
    kk], topi [S, Q, kk], vis [S, Q], work [S, 6])``.  A batch whose
    ``[Q, chunk]`` scores exceed :data:`MAX_BLOCK` is walked in query
    groups; the counters then sum over the groups."""
    Q = qs.shape[0]
    qg = max(8, MAX_BLOCK // chunk // 8 * 8)
    if Q > qg:
        parts = [span_scan(qs[g:g + qg], db, alive, ids, start, lead, size,
                           win_lb[:, g:g + qg], suffix[:, g:g + qg], kk=kk,
                           chunk=chunk, interpret=interpret)
                 for g in range(0, Q, qg)]
        return (jnp.concatenate([p[0] for p in parts], 1),
                jnp.concatenate([p[1] for p in parts], 1),
                jnp.concatenate([p[2] for p in parts], 1),
                sum(p[3] for p in parts))
    S, Tp, n = db.shape
    W = start.shape[1]
    C = chunk
    KP = -(-kk // 128) * 128
    NB = -(-W // 128)

    if Tp % 128 or C % 128:
        raise ValueError(f"span_scan needs 128-row tiles, got Tp={Tp}, "
                         f"chunk={C}")
    idv = jnp.where(alive, ids, -1)[:, None, :]                 # [S, 1, Tp]
    qs = qs.astype(jnp.float32)
    qn = (qs * qs).sum(axis=-1, keepdims=True)                   # [Q, 1]
    lb = _blocks(win_lb, NB)
    sf = _blocks(suffix[..., :W], NB)
    nx = _blocks(suffix[..., 1:], NB)

    n_l = -(-n // 128) * 128
    Q8 = -(-Q // 8) * 8
    vmem = 4 * (2 * C * n_l + 16 * C + 8 * Q8 * C + 9 * Q8 * 128
                + 4 * Q8 * KP + 2 * Q8 * n_l) + (4 << 20)
    table = pl.BlockSpec((None, None, Q, 128),
                         lambda s, i, *_: (s, i // 128, 0, 0))
    top = pl.BlockSpec((None, Q, KP), lambda s, i, *_: (s, 0, 0))
    topd, topi, vis, work = pl.pallas_call(
        functools.partial(_kernel, kk=kk, chunk=C, W=W),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, W),
            in_specs=[
                pl.BlockSpec((Q, n), lambda s, i, *_: (0, 0)),
                pl.BlockSpec((Q, 1), lambda s, i, *_: (0, 0)),
                table, table, table,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                top, top,
                pl.BlockSpec((None, Q, 1), lambda s, i, *_: (s, 0, 0)),
                pl.BlockSpec((None, 8, 128), lambda s, i, *_: (s, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, C, n), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((2, 1, C), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((Q, C), jnp.int32),
                pltpu.VMEM((3, Q, 128), jnp.int32),
                pltpu.SMEM((8,), jnp.int32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((S, Q, KP), jnp.float32),
            jax.ShapeDtypeStruct((S, Q, KP), jnp.int32),
            jax.ShapeDtypeStruct((S, Q, 1), jnp.int32),
            jax.ShapeDtypeStruct((S, 8, 128), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(vmem, 100 << 20)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="span_scan",
    )(start.reshape(-1), lead.reshape(-1), size.reshape(-1), qs, qn, lb,
      sf, nx, idv, db)
    # the kernel holds each entry's row in its shard: map rows to ids
    rows = topi[:, :, :kk]
    held = jnp.take_along_axis(idv, jnp.maximum(rows, 0).reshape(S, 1, -1),
                               axis=2).reshape(rows.shape)
    return (topd[:, :, :kk], jnp.where(rows >= 0, held, -1), vis[:, :, 0],
            work[:, 0, :6])
