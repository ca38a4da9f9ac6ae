"""Registry of every jitted entry point, as (lowering recipe, policy flags).

Each :class:`Entry` names one ``jax.jit`` program the repo ships — the
sharded exact searches (ED, DTW span, DTW lane), the extended (Alg. 4) and
approximate descents, the one-shot LB scan, both build-stage programs, and
the serving head — and knows how to lower it at fixed *audit shapes* on the
audit mesh.  The recipes are the ``core.distributed.lower_*`` helpers, which
build each program exactly as the serving path does, so the audited program
**is** the production program, only smaller.

Audit shapes are deliberately modest (64k × 128 collection, batch 8): the
contract fields the audit checks (collective counts, dtype census, host
round-trips, while/cond counts) are shape-independent structure, and small
shapes keep the full 9-program sweep under ~10 s of compile time.

The audit runs on a fixed 8-way ``data`` mesh so every sharded program
actually partitions (a 1-device mesh would lower the collectives away).
``audit_mesh()`` therefore requires the process to have been started with
``--xla_force_host_platform_device_count=8`` — the CLI
(``python -m repro.analysis.audit``) sets this up itself; in-process
callers must arrange it before jax initializes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

#: devices the audit mesh requires (see module docstring)
AUDIT_DEVICES = 8

#: shared audit shapes — small enough to compile the whole registry in
#: seconds, structured enough that every program keeps its collectives
AUDIT_SHAPES = dict(n_series=1 << 16, length=128, w=16, chunk=2048,
                    n_leaves=1024)
AUDIT_K = 10
AUDIT_NBR = 4
AUDIT_Q_BATCH = 8

#: serving-head audit shapes (vocab retrieval regime: wide k, decode batch)
SERVING_SHAPES = dict(vocab=1 << 14, d_model=128, w=16, n_leaves=512,
                      r_candidates=32, nbr=4, q_batch=8)


@dataclass(frozen=True)
class Entry:
    """One jitted program under audit.

    ``device_path=True`` forbids any f64 op in the compiled module (the
    host f64 re-rank lives *outside* jit by design — an f64 showing up
    in-program is a weak-type promotion leak).  ``sharded=False`` forbids
    collectives entirely (the program is declared shard-local/global)."""
    name: str
    describe: str
    lower: Callable  # mesh -> jax.stages.Lowered
    device_path: bool = True
    sharded: bool = True


def _make_entries() -> tuple[Entry, ...]:
    from repro.core import distributed as D

    s = AUDIT_SHAPES
    k, nbr, qb = AUDIT_K, AUDIT_NBR, AUDIT_Q_BATCH
    return (
        Entry("search_exact_ed",
              "sharded exact ED kNN: windowed span loop + all-gather merge",
              lambda mesh: D.lower_search_sharded(
                  mesh, **s, k=k, q_batch=qb)),
        Entry("search_exact_dtw",
              "sharded exact DTW kNN, shared span order (LB cascade + "
              "masked band DP, DTW_SUB sub-blocking)",
              lambda mesh: D.lower_search_dtw(
                  mesh, **s, k=k, q_batch=qb, order="shared")),
        Entry("search_exact_dtw_lane",
              "sharded exact DTW kNN, cluster lane order (per-query "
              "LB-sorted candidate walk — the serving default)",
              lambda mesh: D.lower_search_dtw(
                  mesh, **s, k=k, q_batch=qb, order="cluster")),
        Entry("search_exact_ed_degraded",
              "degraded-mode sharded exact ED kNN: one dead shard masked "
              "out of the all-gather merge (static shard_health)",
              lambda mesh: D.lower_search_degraded(
                  mesh, **s, k=k, q_batch=qb)),
        Entry("search_extended",
              "sharded extended (Alg. 4) search: subtree descent + sibling "
              "schedule + shard-local leaf scan",
              lambda mesh: D.lower_search_extended(
                  mesh, **s, k=k, nbr=nbr, q_batch=qb)),
        Entry("search_approx",
              "batched approximate descent: root-to-leaf routing + leaf "
              "top-k (shard-local scan + all-gather merge)",
              lambda mesh: D.lower_search_approx(
                  mesh, **s, k=k, nbr=nbr, q_batch=qb)),
        Entry("search_oneshot",
              "one-shot LB scan + exact distances over the batch-sharded "
              "collection (search_step)",
              lambda mesh: D.lower_search_oneshot(
                  mesh, n_series=s["n_series"], length=s["length"],
                  w=s["w"], n_leaves=s["n_leaves"], k=k, q_batch=qb)),
        Entry("build_step",
              "build Stage 1 (SAX table) + root histogram over the "
              "batch-sharded collection (one all-reduce of 2^w ints)",
              lambda mesh: D.lower_build_step(
                  mesh, n_series=s["n_series"], length=s["length"],
                  w=s["w"])),
        Entry("build_bottomup",
              "bottom-up device build grouping: packed-word lexsort + "
              "group delimiting (global, must stay collective-free)",
              lambda mesh: D.lower_build_bottomup(
                  mesh, n_series=s["n_series"], w=s["w"]),
              sharded=False),
        Entry("serving_head",
              "KnnSoftmaxHead retrieval: extended search at serving widths "
              "(device-only, rerank=False)",
              lambda mesh: D.lower_serving_head(mesh, **SERVING_SHAPES)),
        Entry("serving_bucket",
              "coalescing front-end bucket program: extended search with "
              "per-lane traced nbr/metric knobs and dead padding lanes "
              "(one program per bucket shape)",
              lambda mesh: D.lower_search_bucket(
                  mesh, **s, k=k, nbr=nbr, q_batch=qb)),
    )


_ENTRIES: tuple[Entry, ...] | None = None


def entries(names=None) -> tuple[Entry, ...]:
    """All registered programs (lazy: building the tuple imports jax)."""
    global _ENTRIES
    if _ENTRIES is None:
        _ENTRIES = _make_entries()
    if names is None:
        return _ENTRIES
    by_name = {e.name: e for e in _ENTRIES}
    unknown = [n for n in names if n not in by_name]
    if unknown:
        raise KeyError(f"unknown audit entries {unknown}; "
                       f"registered: {sorted(by_name)}")
    return tuple(by_name[n] for n in names)


def names() -> tuple[str, ...]:
    return tuple(e.name for e in entries())


def audit_mesh():
    """The fixed 8-way ``data`` mesh every contract is extracted on."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < AUDIT_DEVICES:
        raise RuntimeError(
            f"compile-contract audit needs {AUDIT_DEVICES} devices, found "
            f"{len(devs)}. Start the process with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={AUDIT_DEVICES} "
            f"(python -m repro.analysis.audit does this automatically).")
    return Mesh(np.array(devs[:AUDIT_DEVICES]).reshape(AUDIT_DEVICES),
                ("data",))
