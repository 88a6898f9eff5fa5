"""Production serving launcher: unified data layer + generator behind a
batched request loop.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \\
      --docs 20000 --requests 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--docs", type=int, default=20_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4, help="requests per serving batch")
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--engine", default="ref", choices=["ref", "pallas"])
    args = ap.parse_args()

    from repro.runtime import configure_compile_cache
    configure_compile_cache()

    from repro.configs import get
    from repro.core import Principal, StoreConfig, TransactionLog, empty
    from repro.data.corpus import DAY_S, CorpusConfig, make_corpus
    from repro.models.transformer import init
    from repro.serving.engine import RAGEngine, Request

    arch = get(args.arch)
    cfg = arch.reduced if args.reduced else arch.full
    rng = np.random.default_rng(0)

    ccfg = CorpusConfig(n_docs=args.docs, dim=args.dim, n_tenants=8)
    scfg = StoreConfig(capacity=1 << (int(np.ceil(np.log2(args.docs))) + 1),
                       dim=args.dim)
    log = TransactionLog(scfg, empty(scfg))
    log.ingest(make_corpus(ccfg))
    params = init(jax.random.PRNGKey(0), cfg)
    engine = RAGEngine(log.snapshot(), cfg, params, k=4, max_prompt=32,
                       max_len=32 + args.tokens + 2, engine=args.engine)

    lat = []
    served = 0
    while served < args.requests:
        n = min(args.batch, args.requests - served)
        reqs = [Request(
            principal=Principal(tenant_id=int(rng.integers(0, 8)),
                                group_bits=0xFFFFFFFF),
            query_emb=rng.standard_normal(args.dim).astype(np.float32),
            prompt_tokens=rng.integers(1, cfg.vocab_size, 5).astype(np.int32),
            min_ts=ccfg.now_ts - 120 * DAY_S, max_new_tokens=args.tokens)
            for _ in range(n)]
        t0 = time.perf_counter()
        engine.serve(reqs)
        lat.append((time.perf_counter() - t0) / n)
        served += n
    lat_ms = np.asarray(lat) * 1e3
    print(f"served {served} requests, per-request p50 {np.percentile(lat_ms, 50):.1f} ms "
          f"p95 {np.percentile(lat_ms, 95):.1f} ms "
          f"({served * args.tokens / sum(lat) / args.batch:.1f} tok/s/req)")


if __name__ == "__main__":
    main()
