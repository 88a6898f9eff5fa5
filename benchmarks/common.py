"""Shared benchmark fixtures: the paper's Section 6.1 setup.

50,000 documents, 128-dim embeddings, 20 tenants, 5 categories, docs uniform
over the past 180 days; 200 iterations per query type; p50/p95/p99 reported.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import RagDB
from repro.core import Predicate, Principal, StoreConfig, TransactionLog, empty
from repro.core.splitstack import SplitStackClient
from repro.data.corpus import DAY_S, CorpusConfig, make_corpus, make_queries

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")

PAPER = {  # the paper's own measured numbers, for side-by-side reporting
    "latency_ms": {
        "pure_similarity": {"A_p50": 0.92, "B_p50": 0.91, "A_p95": 1.1, "B_p95": 0.99},
        "date_filter": {"A_p50": 9.63, "B_p50": 0.75, "A_p95": 10.4, "B_p95": 0.81},
        "tenant_category": {"A_p50": 1.77, "B_p50": 0.46, "A_p95": 1.88, "B_p95": 0.52},
        "full_multi": {"A_p50": 0.43, "B_p50": 0.25, "A_p95": 0.5, "B_p95": 0.3},
    },
    "freshness": {"A_write_ms": 3.54, "B_write_ms": 2.87,
                  "A_window_ms": 3.54, "B_window_ms": 0.0},
    "isolation": {"A_leak_rate": 0.002, "B_leak_rate": 0.0},
    "complexity": {"A_services": 3, "B_services": 1,
                   "A_sync_loc": 1800, "B_sync_loc": 120},
}


def bench_store_cfg(ccfg: CorpusConfig) -> StoreConfig:
    """One arena-size rule for every benchmark stack (next pow2 + headroom),
    so unified and split sides always measure against identical capacity."""
    return StoreConfig(capacity=1 << (int(np.ceil(np.log2(ccfg.n_docs))) + 1),
                       dim=ccfg.dim)


def build_stacks(corpus_cfg: CorpusConfig | None = None, *,
                 filter_bug_rate: float = 0.0, seed: int = 0,
                 with_unified: bool = True):
    """Returns (unified TransactionLog, SplitStackClient, corpus, cfgs).
    `with_unified=False` skips building/ingesting the unified log (None is
    returned) for callers that measure the unified side via build_ragdb."""
    ccfg = corpus_cfg or CorpusConfig()
    scfg = bench_store_cfg(ccfg)
    corpus = make_corpus(ccfg)
    unified = None
    if with_unified:
        unified = TransactionLog(scfg, empty(scfg))
        unified.ingest(corpus)
    split = SplitStackClient(scfg, filter_bug_rate=filter_bug_rate, rng_seed=seed)
    split.ingest(corpus)
    return unified, split, corpus, (ccfg, scfg)


def build_ragdb(corpus_cfg: CorpusConfig | None = None, *, corpus=None,
                **ragdb_kwargs):
    """The unified stack behind the front door: RagDB + ingested corpus.
    Pass `corpus` to reuse one already built (e.g. by build_stacks) instead
    of regenerating it. Extra kwargs reach the RagDB constructor (e.g.
    ``result_cache_size=0`` when a bench must measure the engine path cold
    instead of the session cache)."""
    ccfg = corpus_cfg or CorpusConfig()
    scfg = bench_store_cfg(ccfg)
    if corpus is None:
        corpus = make_corpus(ccfg)
    db = RagDB(scfg, **ragdb_kwargs)
    db.ingest(corpus)
    return db, corpus, (ccfg, scfg)


QUERY_TYPES = {
    # the paper's four complexity levels (Section 6.2)
    "pure_similarity": lambda ccfg: Predicate(),
    "date_filter": lambda ccfg: Predicate(min_ts=ccfg.now_ts - 60 * DAY_S),
    "tenant_category": lambda ccfg: Predicate(tenant=3, cat_mask=0b00110),
    "full_multi": lambda ccfg: Predicate(tenant=3, min_ts=ccfg.now_ts - 60 * DAY_S,
                                         cat_mask=0b00110, acl_bits=0b0011),
}

# the same four levels expressed through the session API; each entry takes
# (db, ccfg, q_emb[, tenant]) and returns a ready QueryBuilder lowering to
# the exact Predicate its QUERY_TYPES twin builds (tenant 3 by default;
# the two admin levels carry no tenant clause and ignore it)
SESSION_QUERIES = {
    "pure_similarity": lambda db, ccfg, q, tenant=3: db.admin_session().search(q),
    "date_filter": lambda db, ccfg, q, tenant=3: (
        db.admin_session().search(q).newer_than(ccfg.now_ts - 60 * DAY_S)),
    "tenant_category": lambda db, ccfg, q, tenant=3: (
        db.session(Principal(tenant_id=tenant, group_bits=0xFFFFFFFF))
        .search(q).in_categories([1, 2])),
    "full_multi": lambda db, ccfg, q, tenant=3: (
        db.session(Principal(tenant_id=tenant, group_bits=0b0011))
        .search(q).newer_than(ccfg.now_ts - 60 * DAY_S).in_categories([1, 2])),
}


def percentiles(samples_s: list[float]) -> dict:
    a = np.asarray(samples_s) * 1e3
    return {"p50": float(np.percentile(a, 50)), "p95": float(np.percentile(a, 95)),
            "p99": float(np.percentile(a, 99)), "mean": float(a.mean())}


def timeit(fn, *, iters: int, warmup: int = 5) -> list[float]:
    """Monotonic-clock timing with a block_until_ready audit: whatever
    ``fn`` returns is synced inside the timed region, so an async device
    launch is never credited as free. (Non-array returns pass through
    block_until_ready untouched; fns that sync internally pay nothing.)"""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    out = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return out


def save_result(name: str, payload: dict) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path
