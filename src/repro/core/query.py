"""The unified query — the paper's Section 5.2 as one fused device program.

    SELECT content, embedding <=> :q AS distance
    FROM documents
    WHERE tenant_id = :tenant
      AND updated_at > :min_ts
      AND category = ANY(:cats)
      AND :principal = ANY(permitted_users)
    ORDER BY distance LIMIT :k;

becomes: predicate mask (engine-level, evaluated over metadata columns in the
same pass as similarity) -> masked scores -> top-k. There is no code path
that can return an unmasked row: the leakage-impossibility property the paper
attributes to row-level security holds here at the kernel level, and is
property-tested in tests/test_core_query.py.

Two execution engines share this contract:
  * `unified_query_ref`    — pure-jnp reference (this file)
  * `repro.kernels.arena_scan.ops.arena_scan` — the arena scan (Pallas
    kernel on a TPU, its jnp streaming scan elsewhere)
`unified_query` and `unified_query_grouped` dispatch on `engine=`.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.store import Store
from repro.kernels.arena_scan.ops import arena_scan

NEG_INF = jnp.float32(jnp.finfo(jnp.float32).min)


@dataclasses.dataclass(frozen=True)
class Predicate:
    """Runtime predicate values. Disabled clauses use their pass-all value, so
    the jitted program is shared across every clause combination (one compiled
    engine, like one SQL planner).

    tenant   : int32, -2 means "any tenant" (-1 is the tombstone tenant)
    min_ts   : int32 inclusive lower bound on updated_at (0 = no recency bound)
    cat_mask : uint32 bitmask of allowed categories (all-ones = any category)
    acl_bits : uint32 principal group bits; rows must share a bit (all-ones = no ACL)
    """
    tenant: int = -2
    min_ts: int = 0
    cat_mask: int = 0xFFFFFFFF
    acl_bits: int = 0xFFFFFFFF

    def as_array(self) -> jax.Array:
        # [tenant, min_ts, cat_mask, acl_bits] packed for the kernel path.
        # Memoized with LRU eviction: predicates repeat across a serving
        # session, and the host->device transfer would otherwise dominate
        # sub-ms queries. Eviction is per-entry (oldest use first) so a hot
        # predicate is never dropped by a burst of one-off ones.
        cached = _PRED_CACHE.get(self)
        if cached is None:
            cached = jnp.array(
                [self.tenant, self.min_ts,
                 jnp.uint32(self.cat_mask).view(jnp.int32),
                 jnp.uint32(self.acl_bits).view(jnp.int32)], dtype=jnp.int32)
            while len(_PRED_CACHE) >= _PRED_CACHE_CAP:
                _PRED_CACHE.popitem(last=False)
            _PRED_CACHE[self] = cached
        else:
            _PRED_CACHE.move_to_end(self)
        return cached


_PRED_CACHE: OrderedDict["Predicate", jax.Array] = OrderedDict()
_PRED_CACHE_CAP = 4096


def predicate_mask(store: Store, pred: jax.Array) -> jax.Array:
    """Engine-level WHERE clause. pred = Predicate.as_array() (4,) int32.

    Returns (N,) bool — True where the row is live AND satisfies every clause.
    """
    tenant, min_ts = pred[0], pred[1]
    cat_mask = pred[2].view(jnp.uint32)
    acl_bits = pred[3].view(jnp.uint32)
    live = store["tenant"] >= 0                                   # tombstones out
    ten_ok = jnp.where(tenant == -2, True, store["tenant"] == tenant)
    ts_ok = store["updated_at"] >= min_ts
    cat_ok = (jnp.left_shift(jnp.uint32(1), store["category"].astype(jnp.uint32))
              & cat_mask) != 0
    acl_ok = (store["acl"] & acl_bits) != 0
    return live & ten_ok & ts_ok & cat_ok & acl_ok


@partial(jax.jit, static_argnames=("k",))
def unified_query_ref(store: Store, q: jax.Array, pred: jax.Array, k: int):
    """q: (B, D) (normalized by the caller for cosine) -> (scores (B,k) f32,
    slots (B,k) int32). Slots of masked-out rows never appear: their score is
    -inf, and if fewer than k rows qualify the tail slots are -1. LIMIT k
    larger than the arena returns every qualifying row (SQL semantics),
    padded to k."""
    n = store["emb"].shape[0]
    mask = predicate_mask(store, pred)                            # (N,)
    # exact f32, as in the arena-scan stages (a TPU's default f32 matmul
    # rounds to bf16 and would rank differently from the pallas engine)
    scores = jnp.matmul(q.astype(jnp.float32),
                        store["emb"].astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST)      # (B, N)
    scores = jnp.where(mask[None, :], scores, NEG_INF)
    k_eff = min(k, n)
    top_scores, top_idx = jax.lax.top_k(scores, k_eff)
    top_idx = jnp.where(top_scores > NEG_INF, top_idx, -1)
    if k_eff < k:
        pad = ((0, 0), (0, k - k_eff))
        top_scores = jnp.pad(top_scores, pad, constant_values=NEG_INF)
        top_idx = jnp.pad(top_idx, pad, constant_values=-1)
    return top_scores, top_idx


def make_sharded_query(mesh, axes, n_rows: int, k: int,
                       placement_kind: str = "hash"):
    """Distributed unified query (§Perf iteration: rag-unified/query_hot).

    The naive GSPMD lowering of `unified_query_ref` over a row-sharded corpus
    all-gathers the FULL (B, N) score matrix to run the global top-k — 17 GiB
    per device at the 2^26-doc hot tier. This version runs the same masked
    scan per shard, keeps only each shard's local top-k, and merges a
    constant-size (shards x k) candidate list: collective payload drops from
    O(B x N) to O(B x shards x k), independent of corpus size.

    Thin wrapper over `repro.kernels.arena_scan.sharded.make_sharded_arena_scan`
    (the full engine entry point, which additionally returns the per-shard
    `rows_scanned` audit vector) keeping the 2-output contract this module has
    always exposed. Selection is exact lexicographic (score desc, global
    doc_id asc) — placement-invariant by construction.
    """
    from repro.kernels.arena_scan.sharded import make_sharded_arena_scan
    fn = make_sharded_arena_scan(mesh, axes, n_rows, k,
                                 placement_kind=placement_kind)

    def query(store, q, pred):
        scores, slots, _rows = fn(store, q, pred)
        return scores, slots

    return query


def unified_query(store: Store, q: jax.Array, pred: Predicate, k: int,
                  engine: str = "ref", page_rows: int | None = None,
                  tally: bool = False):
    """Front door used by the serving engine / benchmarks. The resident
    ``ref`` engine is the store-level reference `unified_query_ref`; every
    other launch is the G = 1 grouped scan.

    ``page_rows`` selects the paged arena-scan regime (HBM-resident arena
    streamed in page tiles — `repro.kernels.arena_scan`): the pallas engine
    switches to explicit double-buffered DMA, the ref engine to the
    streaming jnp scan tiled at the page size. Results are bit-identical to
    the resident regime (the arena-scan conformance contract).
    ``tally=True`` appends the kernel's merge tally (`arena_scan`; None
    where no kernel ran)."""
    pa = pred.as_array()
    if engine == "ref" and page_rows is None:
        out = unified_query_ref(store, q, pa, k)
        return out + (None,) if tally else out
    gids = jnp.zeros((q.shape[0],), jnp.int32)
    return unified_query_grouped(store, q, gids, pa[None, :], k,
                                 engine=engine, page_rows=page_rows,
                                 tally=tally)


#: Blocker predicate for padding a stacked (G, 4) predicate list to a pow2
#: group count: tenant -3 matches no live row (live rows have tenant >= 0 and
#: -3 is not the "any tenant" sentinel -2), so a padding group masks the
#: whole arena and — since no real query row carries its group id — cannot
#: perturb any real group's results.
BLOCK_ALL = Predicate(tenant=-3)


def stack_predicates(preds) -> jax.Array:
    """Stack lowered predicates into the (G, 4) int32 array the grouped scan
    consumes (each row is `Predicate.as_array()`, so the per-predicate
    device cache is reused).

    >>> stack_predicates([Predicate(), Predicate(tenant=3)]).shape
    (2, 4)
    """
    return jnp.stack([p.as_array() for p in preds])


def unified_query_grouped(store: Store, q: jax.Array, gids, preds, k: int,
                          engine: str = "ref", page_rows: int | None = None,
                          tally: bool = False):
    """Grouped front door: ONE arena scan answers every predicate group.

    q: (B, D) stacked query rows across ALL groups; gids: (B,) int32 group
    id per row; preds: a list of G `Predicate`s (or a pre-stacked (G, 4)
    int32 array). Per query row the result is exactly
    ``unified_query(store, q[row], preds[gids[row]], k)`` — the fused scan
    changes how many times the arena streams (once, not G times), never
    what any row may see. ``page_rows`` selects the paged arena-scan regime
    (bit-identical; see `unified_query`). Returns (scores (B, k),
    slots (B, k)), and with ``tally=True`` the merge tally after them."""
    pa = (stack_predicates(preds) if isinstance(preds, (list, tuple))
          else jnp.asarray(preds, jnp.int32))
    if engine not in ("ref", "pallas"):
        raise ValueError(f"unknown engine {engine!r}")
    return arena_scan(q, store["emb"], store["tenant"], store["updated_at"],
                      store["category"], store["acl"], gids, pa, k,
                      use_kernel=engine == "pallas", page_rows=page_rows,
                      tally=tally)
