"""Device time of one resident hybrid arena scan, split into its parts.

    python tools/hybrid_pass_bench.py --rows 1048576 --terms 64 [--k 1]

Times `arena_scan` at a hybrid spec (the compiled Pallas kernel) on a
seeded arena of
``--rows`` x 768 f32 rows with ``--terms`` postings lanes, at 8 query rows
(one pass) for each fusion mode and query-term bucket, against the dense
grouped scan of the same arena and rows. Prints one JSON line per shape:
ms per call (mean of ``--iters`` back-to-back calls after a warm call) and
the compile-and-first-call time. ``--k`` is the LIMIT (10 by default):
at ``--k 1`` the kernel's running-list merge is one selection round in
place of ten, so the two runs bound what the merge costs a pass. Needs a
TPU.
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
    ap.add_argument("--k", type=int, default=10)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp

    from repro.kernels.arena_scan.ops import arena_scan
    from repro.kernels.arena_scan.stages import hybrid_spec

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    n, d, t, v, b, k = (args.rows, args.dim, args.terms, args.vocab, 8,
                        args.k)
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
        ms, first = timed(lambda: arena_scan(
            q, emb, tenant, ts, cat, acl, gids, preds_of(g), k))
        print(json.dumps({"scan": "dense", "groups": g, "k": k, "ms": ms,
                          "first_s": first}), flush=True)
        for mode in ("wsum", "rrf"):
            for qt in (int(x) for x in args.qts.split(",")):
                qterms = jax.random.randint(jax.random.key(qt), (b, qt), 0,
                                            v, jnp.int32)
                ms, first = timed(lambda: arena_scan(
                    q, emb, tenant, ts, cat, acl, gids, preds_of(g), k,
                    spec=hybrid_spec(mode), terms=terms, lexnorm=lexnorm,
                    idf=idf, qterms=qterms))
                print(json.dumps({"scan": "hybrid", "mode": mode, "qt": qt,
                                  "groups": g, "lanes": t, "k": k, "ms": ms,
                                  "first_s": first}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
