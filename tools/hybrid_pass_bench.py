"""Device time of one resident hybrid arena scan, split into its parts.

    python tools/hybrid_pass_bench.py --rows 1048576 --terms 64

Times `hybrid_score` (the compiled Pallas kernel) on a seeded arena of
``--rows`` x 768 f32 rows with ``--terms`` postings lanes, at 8 query rows
(one pass) for each fusion mode and query-term bucket, against the dense
grouped scan of the same arena and rows. Prints one JSON line per shape:
ms per call (mean of ``--iters`` back-to-back calls after a warm call) and
the compile-and-first-call time. Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--dim", type=int, default=768)
    ap.add_argument("--terms", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=30522)
    ap.add_argument("--qts", default="1,4,8,16")
    ap.add_argument("--groups", default="1,16")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp

    from repro.kernels.grouped_topk.ops import grouped_topk
    from repro.kernels.hybrid_score.ops import hybrid_score

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    n, d, t, v, b, k = args.rows, args.dim, args.terms, args.vocab, 8, 10
    key = jax.random.key(7)
    ks = jax.random.split(key, 8)
    emb = jax.random.normal(ks[0], (n, d), jnp.float32)
    emb = emb / jnp.linalg.norm(emb, axis=1, keepdims=True)
    tenant = jax.random.randint(ks[1], (n,), 0, 20, jnp.int32)
    ts = jax.random.randint(ks[2], (n,), 0, 1000, jnp.int32)
    cat = jax.random.randint(ks[3], (n,), 0, 5, jnp.int32)
    acl = jnp.full((n,), 0xFF, jnp.uint32)
    terms = jax.random.randint(ks[4], (n, t), -1, v, jnp.int32)
    lexnorm = jnp.where(terms >= 0, jax.random.uniform(ks[5], (n, t)), 0.0)
    idf = jax.random.uniform(ks[6], (v,), jnp.float32, 0.0, 8.0)
    q = jax.random.normal(ks[7], (b, d), jnp.float32)
    jax.block_until_ready((emb, terms, lexnorm))

    def timed(fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3, first

    def preds_of(g):
        p = jnp.zeros((g, 4), jnp.int32)
        p = p.at[:, 0].set(jnp.arange(g, dtype=jnp.int32) % 20)
        return p.at[:, 2].set(-1).at[:, 3].set(-1)

    gids = jnp.arange(b, dtype=jnp.int32) % 2
    for g in (int(x) for x in args.groups.split(",")):
        ms, first = timed(lambda: grouped_topk(
            q, emb, tenant, ts, cat, acl, gids, preds_of(g), k))
        print(json.dumps({"scan": "dense", "groups": g, "ms": ms,
                          "first_s": first}), flush=True)
        for mode in ("wsum", "rrf"):
            for qt in (int(x) for x in args.qts.split(",")):
                qterms = jax.random.randint(jax.random.key(qt), (b, qt), 0,
                                            v, jnp.int32)
                ms, first = timed(lambda: hybrid_score(
                    q, emb, tenant, ts, cat, acl, terms, lexnorm, idf, gids,
                    preds_of(g), qterms, k, mode=mode))
                print(json.dumps({"scan": "hybrid", "mode": mode, "qt": qt,
                                  "groups": g, "lanes": t, "ms": ms,
                                  "first_s": first}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
