"""Three-tier deployment router — paper §7.3.

  Tier 1 HOT   unified store (this paper): recent docs / hot tenants; full
               predicate model, transactional freshness. 10-30 % of corpus,
               80-90 % of traffic.
  Tier 2 WARM  similarity-only store (a "specialized vector DB"): long-tail
               corpus where pure ANN dominates; metadata fetched separately
               (coordination cost accepted for this workload class only).
  Tier 3 COLD  host archive ("object storage"): explicit fetch by doc id,
               no vector index, no device residency.

The router preserves the paper's key claim at scale: multi-constraint queries
never leave the unified tier; only low-constraint long-tail similarity spills
to the warm tier.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.query import Predicate
from repro.core.splitstack import SplitStackClient
from repro.core.store import DocBatch, StoreConfig, empty
from repro.core.transactions import TransactionLog


@dataclasses.dataclass
class RouteStats:
    """Counters are per query ROW (a (B, D) call counts B), matching the
    front-door ExecStats so shim and session traffic aggregate coherently."""
    hot_queries: int = 0
    warm_queries: int = 0
    cold_fetches: int = 0


class TieredResult(tuple):
    """The (scores, slots, tiers) triple `TieredRouter.query` returns, with
    the planner's decisions attached as metadata: ``.engine`` is the engine
    that actually ran ("ref" | "pallas" | "sharded") and ``.route`` the tier
    route ("hot" | "hot+warm"). Callers that unpack three values keep
    working; callers that need provenance no longer have to re-derive the
    plan via a separate explain() call. Documented in docs/api.md."""

    def __new__(cls, scores, slots, tiers, *, engine: str, route: str):
        self = super().__new__(cls, (scores, slots, tiers))
        self.engine = engine
        self.route = route
        return self


class TieredRouter:
    def __init__(self, hot_cfg: StoreConfig, warm_cfg: StoreConfig, *,
                 hot_window_s: int, now_ts: int, hot_placement=None):
        # hot_placement: optional core.store.ShardPlacement — a mesh-built
        # RagDB routes hot-tier slot allocation through per-shard regions
        self.hot = TransactionLog(hot_cfg, empty(hot_cfg, hot_placement),
                                  placement=hot_placement)
        self.warm = SplitStackClient(warm_cfg)
        self.cold: dict[int, dict[str, Any]] = {}
        self.hot_window_s = hot_window_s
        self.now_ts = now_ts
        self.stats = RouteStats()

    # -- ingest: placement policy ---------------------------------------
    def ingest(self, batch: DocBatch) -> None:
        ts = np.asarray(batch.updated_at)
        hot_sel = ts >= self.now_ts - self.hot_window_s
        idx_hot = np.nonzero(hot_sel)[0]
        idx_warm = np.nonzero(~hot_sel)[0]

        def take(sel):
            s = jnp.asarray(sel, jnp.int32)
            return DocBatch(emb=batch.emb[s], tenant=batch.tenant[s],
                            category=batch.category[s], updated_at=batch.updated_at[s],
                            acl=batch.acl[s], doc_id=batch.doc_id[s],
                            terms=None if batch.terms is None else batch.terms[s],
                            tfs=None if batch.tfs is None else batch.tfs[s])

        if len(idx_hot):
            # an all-hot batch (single-tier mode) goes in as it is
            self.hot.ingest(batch if len(idx_warm) == 0 else take(idx_hot))
        if len(idx_warm):
            self.warm.ingest(take(idx_warm))

    def archive(self, doc_id: int, payload: dict[str, Any]) -> None:
        self.cold[doc_id] = payload

    # -- query routing ---------------------------------------------------
    def query(self, q: jax.Array, pred: Predicate, k: int, *,
              engine: str | None = None) -> "TieredResult":
        """Compatibility shim over the front-door planner/executor (the
        routing rule itself now lives in repro.api.planner.choose_route):
        multi-constraint queries within the hot window stay hot-only;
        long-tail similarity additionally probes the warm tier and merges.

        ``engine=None`` (the default) lets the planner choose; pass a name
        to force one. The returned `TieredResult` unpacks as the usual
        (scores, slots, tiers) triple and carries ``.engine`` / ``.route``
        so callers can tell ref from pallas without a separate explain()."""
        # imported lazily: repro.api's package init imports this module
        from repro.api.executor import query_tiered
        from repro.api.plan import logical_from_predicate
        from repro.api.planner import choose_engine, choose_route

        logical = logical_from_predicate(pred, k=k, engine=engine)
        snap = self.hot.snapshot()
        eng, _ = choose_engine(logical, n_rows=snap["emb"].shape[0])
        route, _ = choose_route(logical, hot_window_s=self.hot_window_s,
                                now_ts=self.now_ts, warm_rows=self.warm.n_docs)
        self.stats.hot_queries += q.shape[0]
        if route == "hot+warm":
            self.stats.warm_queries += q.shape[0]
        s, sl, tr = query_tiered(snap, self.warm, q, pred, k,
                                 engine=eng, probe_warm=(route == "hot+warm"))
        return TieredResult(s, sl, tr, engine=eng, route=route)

    def fetch_cold(self, doc_id: int):
        self.stats.cold_fetches += 1
        return self.cold.get(doc_id)
