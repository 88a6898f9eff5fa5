"""Quickstart: the paper in 80 lines.

Builds a multi-tenant corpus, ingests it into BOTH stacks, then shows the
three failure modes of the split stack and their absence in the unified one:
latency under constraints, the inconsistency window, and tenant leakage.

The unified stack is driven through its front door — `RagDB` sessions with a
composable query builder that compiles to an explainable physical plan — so
this is also the 10-line tour of the API.

  PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax.numpy as jnp
import numpy as np

from repro.api import RagDB
from repro.core import Principal, StoreConfig
from repro.core.splitstack import SplitStackClient
from repro.data.corpus import DAY_S, CorpusConfig, make_corpus, make_queries
from repro.runtime import configure_compile_cache

configure_compile_cache()

ccfg = CorpusConfig(n_docs=20_000, dim=64, n_tenants=8, n_categories=5)
scfg = StoreConfig(capacity=1 << 15, dim=64)
corpus = make_corpus(ccfg)

print("== ingest into both stacks ==")
db = RagDB(scfg)
db.ingest(corpus)
split = SplitStackClient(scfg, filter_bug_rate=1.0)  # bug always fires (demo)
split.ingest(corpus)
print(f"unified: {int(db.log.snapshot()['n_live'])} docs, "
      f"commit_ts={int(db.log.snapshot()['commit_ts'])}")

print("\n== the unified query: similarity + freshness + category + RLS ==")
q = make_queries(ccfg, 1, batch=1)[0]
session = db.session(Principal(tenant_id=3, group_bits=0b0011))
builder = (session.search(np.asarray(q)[0], normalize=False)
           .newer_than(ccfg.now_ts - 60 * DAY_S)
           .in_categories([1, 2])
           .limit(5))
print(builder.explain())
t0 = time.perf_counter()
res = builder.run()
t_unified = time.perf_counter() - t0
slots = res.slots[0]
tenant_of = np.asarray(corpus.tenant)
print(f"top-5 slots {slots.tolist()}  tenants {tenant_of[slots[slots>=0]].tolist()} "
      f" ({t_unified*1e3:.1f} ms, one device program)")

print("\n== the same query on the split stack ==")
pred = builder.lower().predicate()      # identical clause set, old entrance
t0 = time.perf_counter()
_, slots_a = split.query(q, pred, k=5)
t_split = time.perf_counter() - t0
got = slots_a[0][slots_a[0] >= 0]
leaked = (tenant_of[got] != session.principal.tenant_id).sum()
print(f"round trips: {split.stats.round_trips}, retries: {split.stats.retries} "
      f"({t_split*1e3:.1f} ms)")
print(f"LEAKED {leaked}/{len(got)} docs from other tenants "
      f"(app-layer tenant filter bug active)")
print("unified leaked 0 by construction — the predicate runs inside the kernel")

print("\n== freshness: atomic vs two-phase writes ==")
rng = np.random.default_rng(0)
new_emb = rng.standard_normal((4, 64), dtype=np.float32)
db.update([0, 1, 2, 3], jnp.asarray(new_emb), [ccfg.now_ts] * 4)
split.write_gap_s = 0.003
split.update([0, 1, 2, 3], new_emb, [ccfg.now_ts] * 4)
print(f"unified inconsistency window: {db.log.inconsistency_window_s*1e3:.2f} ms "
      f"(embedding+metadata commit in ONE program)")
print(f"split inconsistency window:   "
      f"{split.stats.inconsistency_windows_s[-1]*1e3:.2f} ms "
      f"(reader sees new vector + stale metadata in the gap)")
