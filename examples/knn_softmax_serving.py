"""Decode with a softmax approximated by Dumpy kNN retrieval — the paper's
motivating application #3 (kNN-softmax [69]).

The serving half draws each decode step's hidden states near seeded target
columns of a seeded output embedding and routes them through the
continuous-batching front-end (``repro.serving.batching``,
docs/serving.md): each decode row submits as a single request and the
front-end coalesces them into bucketed device programs.

    PYTHONPATH=src python examples/knn_softmax_serving.py [--batch 4] \
        [--tokens 24] [--max-wait 0.002]
"""
import argparse
import time

import numpy as np

from repro.serving.knn_softmax import KnnSoftmaxHead


def standalone_head_demo() -> None:
    """Retrieval quality vs exact softmax on a synthetic output embedding."""
    rng = np.random.default_rng(0)
    d, vocab = 64, 8192
    lm_head = rng.standard_normal((d, vocab)).astype(np.float32) / np.sqrt(d)
    head = KnnSoftmaxHead(lm_head, w=8, th=256, r_candidates=512, nbr_nodes=8)
    for _ in range(60):
        tgt = rng.integers(vocab)
        h = lm_head[:, tgt] + 0.3 * rng.standard_normal(d).astype(np.float32)
        head.step(h)
    s = head.stats
    print(f"[standalone] kNN-softmax over vocab={vocab}: "
          f"retrieval recall={s.exact_in_topr/s.tokens:.0%}, "
          f"argmax agreement={s.agree_argmax/s.tokens:.0%} "
          f"(paper §1: ≥80% recall ≈ exact-softmax accuracy)")


def frontend_decode_demo(batch: int, tokens: int, max_wait: float) -> None:
    """Batched decode steps whose retrieval runs through the front-end."""
    rng = np.random.default_rng(0)
    d, vocab = 64, 512
    lm_head = rng.standard_normal((d, vocab)).astype(np.float32) / np.sqrt(d)
    head = KnnSoftmaxHead(lm_head, th=64, r_candidates=64, nbr_nodes=8)
    # warms the bucket ladder once; every decode row is then one request
    frontend = head.make_frontend(max_batch=max(batch, 4), max_wait=max_wait)
    out_tokens = []
    t0 = time.time()
    for _ in range(tokens - 1):
        tgt = rng.integers(vocab, size=batch)
        noise = rng.standard_normal((batch, d)).astype(np.float32)
        H = lm_head[:, tgt].T + 0.3 / np.sqrt(d) * noise
        out_tokens.append(head.step_batch_via(frontend, H))
    dt = time.time() - t0
    frontend.close()
    print(f"decoded {tokens-1} steps x{batch} in {dt:.2f}s "
          f"({(tokens-1)*batch/max(dt,1e-9):.1f} tok/s)")
    s = head.stats
    print(f"knn-softmax stats: recall@R="
          f"{s.exact_in_topr/max(s.tokens,1):.2f} "
          f"argmax-agree={s.agree_argmax/max(s.tokens,1):.2f}")
    print(f"frontend stats: {frontend.stats.snapshot()}")
    print("sample:", np.stack(out_tokens, axis=1)[0][:16])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--max-wait", type=float, default=0.002,
                    help="front-end coalescing deadline (seconds)")
    args = ap.parse_args()
    standalone_head_demo()
    print("[serving] batched decode with the Dumpy retrieval head:")
    frontend_decode_demo(args.batch, args.tokens, args.max_wait)


if __name__ == "__main__":
    main()
