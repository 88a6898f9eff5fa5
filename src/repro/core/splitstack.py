"""Stack A — the conventional three-tool RAG stack, faithfully reproduced.

Three "services", three consistency domains:
  1. VectorStore    — embeddings only; answers pure ANN top-k. Knows nothing
                      about tenants, timestamps, or permissions.
  2. MetadataStore  — relational columns, queried by row id (a separate device
                      program = a separate system round trip).
  3. MetadataCache  — host-side TTL cache in front of the metadata store (the
                      paper's third tool), a second source of staleness.

Everything in this file is the "synchronization code" the paper counts
(~1,800 LOC in production systems; Table 4): over-fetch heuristics, app-layer
post-filtering, retry-on-underfill, two-phase writes, cache invalidation.
The injectable `filter_bug_rate` models the app-layer tenant-filter bug behind
the paper's measured 0.2 % leakage (Table 3) — the point is that in Stack A
such a bug is *possible*, while in the unified engine the tenant predicate is
evaluated inside the retrieval kernel and no application code can skip it.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.query import NEG_INF, Predicate
from repro.core.store import DocBatch, StoreConfig, normalize
from repro.serving.faults import FaultPlan, FaultRule, WarmTierError


# ---------------------------------------------------------------------------
# tool 1: the vector database (similarity only)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k",))
def vector_topk(emb: jax.Array, valid: jax.Array, q: jax.Array, k: int):
    scores = q.astype(jnp.float32) @ emb.astype(jnp.float32).T
    scores = jnp.where(valid[None, :], scores, NEG_INF)
    return jax.lax.top_k(scores, k)


def _warm_keep(valid: jax.Array, meta: dict[str, jax.Array],
               pred: jax.Array) -> jax.Array:
    """The warm tier's pushed-down WHERE clause: live & tenant & recency &
    category & ACL over the warm metadata columns. ONE definition shared by
    every warm scan that accepts a lowered predicate (dense and hybrid), so
    the clause semantics cannot desynchronize between them."""
    tenant = meta["tenant"]
    keep = valid & (tenant >= 0)
    keep &= (pred[0] == -2) | (tenant == pred[0])
    keep &= meta["updated_at"] >= pred[1]
    cat_mask = pred[2].view(jnp.uint32)
    acl_bits = pred[3].view(jnp.uint32)
    keep &= (jnp.left_shift(jnp.uint32(1),
                            meta["category"].astype(jnp.uint32)) & cat_mask) != 0
    keep &= (meta["acl"] & acl_bits) != 0
    return keep


@partial(jax.jit, static_argnames=("k",))
def vector_topk_filtered(emb: jax.Array, valid: jax.Array,
                         meta: dict[str, jax.Array], q: jax.Array,
                         pred: jax.Array, k: int):
    """Predicate PUSHDOWN: the vector service accepts the lowered predicate
    and masks inside the scan (what production vector DBs call metadata
    filtering). One program, no over-fetch, no under-fill retries — and the
    filter cannot be skipped by app code, so the warm tier inherits the
    unified engine's isolation construction when queried this way."""
    keep = _warm_keep(valid, meta, pred)
    scores = q.astype(jnp.float32) @ emb.astype(jnp.float32).T
    scores = jnp.where(keep[None, :], scores, NEG_INF)
    top_s, top_i = jax.lax.top_k(scores, k)
    return top_s, jnp.where(top_s > NEG_INF, top_i, -1)


@jax.jit
def vector_write(emb: jax.Array, valid: jax.Array, slots: jax.Array, new_emb: jax.Array):
    return emb.at[slots].set(new_emb), valid.at[slots].set(True)


@partial(jax.jit, static_argnames=("k", "mode", "w_dense", "w_lex", "rrf_c",
                                   "lists"))
def vector_topk_hybrid(emb: jax.Array, valid: jax.Array,
                       meta: dict[str, jax.Array], terms: jax.Array,
                       lexnorm: jax.Array, idf: jax.Array, q: jax.Array,
                       pred: jax.Array, qterms: jax.Array, k: int,
                       mode: str, w_dense: float, w_lex: float,
                       rrf_c: float, lists: bool):
    """Hybrid dense+BM25 pushdown for the warm tier: the lowered predicate
    AND the lexical scoring both run inside the one scan — the exact
    warm-tier analogue of `vector_topk_filtered`'s pushdown contract (one
    round trip, no app-layer filter in the loop), extended with the second
    signal. idf/avgdl come from the CORPUS-GLOBAL `LexicalStats`, so warm
    BM25 scores are comparable with hot ones across the tier merge."""
    from repro.kernels.arena_scan.ref import bm25_block, qidf_of, rrf_fuse
    keep = _warm_keep(valid, meta, pred)
    qidf = qidf_of(idf, qterms)
    if mode == "wsum":
        # fold the fusion weights into the inputs, exactly as the hot-tier
        # engines do (arena_scan pinning rule 1) — warm and hot wsum scores
        # stay comparable AND bit-consistent across the tier merge
        q = q * jnp.float32(w_dense)
        qidf = qidf * jnp.float32(w_lex)
    dense = q.astype(jnp.float32) @ emb.astype(jnp.float32).T
    bm25 = bm25_block(terms, lexnorm, qterms, qidf)
    if mode == "wsum":
        fused = jnp.where(keep[None, :], dense + bm25, NEG_INF)
        top_s, top_i = jax.lax.top_k(fused, k)
        return top_s, jnp.where(top_s > NEG_INF, top_i, -1)
    d_s, d_i = jax.lax.top_k(jnp.where(keep[None, :], dense, NEG_INF), k)
    l_s, l_i = jax.lax.top_k(jnp.where(keep[None, :], bm25, NEG_INF), k)
    d_i = jnp.where(d_s > NEG_INF, d_i, -1)
    l_i = jnp.where(l_s > NEG_INF, l_i, -1)
    if lists:
        return d_s, d_i, l_s, l_i
    return rrf_fuse(d_s, d_i, l_s, l_i, k, rrf_c)


# ---------------------------------------------------------------------------
# tool 2: the relational metadata store (lookup by id)
# ---------------------------------------------------------------------------

@jax.jit
def metadata_lookup(meta: dict[str, jax.Array], idx: jax.Array):
    return {k: v[idx] for k, v in meta.items()}


@jax.jit
def metadata_write(meta: dict[str, jax.Array], slots: jax.Array,
                   tenant: jax.Array, category: jax.Array,
                   updated_at: jax.Array, acl: jax.Array, doc_id: jax.Array):
    return {
        "tenant": meta["tenant"].at[slots].set(tenant),
        "category": meta["category"].at[slots].set(category),
        "updated_at": meta["updated_at"].at[slots].set(updated_at),
        "acl": meta["acl"].at[slots].set(acl),
        "doc_id": meta["doc_id"].at[slots].set(doc_id),
    }


# ---------------------------------------------------------------------------
# tool 3: host-side metadata cache (TTL)
# ---------------------------------------------------------------------------

class MetadataCache:
    def __init__(self, ttl_s: float = 1.0):
        self.ttl_s = ttl_s
        self._entries: dict[int, tuple[float, tuple]] = {}
        self.hits = 0
        self.misses = 0

    def get(self, slot: int):
        ent = self._entries.get(slot)
        if ent is not None and time.perf_counter() - ent[0] < self.ttl_s:
            self.hits += 1
            return ent[1]
        self.misses += 1
        return None

    def put(self, slot: int, row: tuple):
        self._entries[slot] = (time.perf_counter(), row)

    def invalidate(self, slots):
        for s in slots:
            self._entries.pop(int(s), None)


# ---------------------------------------------------------------------------
# the glue: Stack A client
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SplitStackStats:
    round_trips: int = 0
    retries: int = 0
    inconsistency_windows_s: list = dataclasses.field(default_factory=list)
    write_latencies_s: list = dataclasses.field(default_factory=list)


class SplitStackClient:
    """Application code stitching the three tools together."""

    OVERFETCH = 4          # initial over-fetch multiplier
    MAX_RETRIES = 4        # each retry quadruples the fetch size (last one
                           # typically degenerates to a full scan — the
                           # "query composition explosion" failure mode)

    def __init__(self, cfg: StoreConfig, *, filter_bug_rate: float = 0.0,
                 cache_ttl_s: float = 1.0, rng_seed: int = 0, faults=None):
        N, D = cfg.capacity, cfg.dim
        self.cfg = cfg
        self.emb = jnp.zeros((N, D), jnp.dtype(cfg.dtype))
        self.valid = jnp.zeros((N,), bool)
        self.meta = {
            "tenant": jnp.full((N,), -1, jnp.int32),
            "category": jnp.zeros((N,), jnp.int32),
            "updated_at": jnp.zeros((N,), jnp.int32),
            "acl": jnp.zeros((N,), jnp.uint32),
            "doc_id": jnp.full((N,), -1, jnp.int32),
        }
        self.cache = MetadataCache(cache_ttl_s)
        self.stats = SplitStackStats()
        self.filter_bug_rate = filter_bug_rate
        # Unified injection surface (serving.faults): the legacy
        # filter_bug_rate kwarg is now a shim that installs a
        # ``split.filter_bug`` rule on a FaultPlan seeded by rng_seed, so
        # bench_isolation and the chaos harness share ONE seeded mechanism.
        # A caller-supplied plan may also carry warm.error / warm.stall
        # rules, which fire on the pushdown (warm-tier) query paths.
        if faults is None:
            faults = FaultPlan(seed=rng_seed)
        if filter_bug_rate > 0.0 and "split.filter_bug" not in faults.rules:
            faults.rules["split.filter_bug"] = FaultRule(rate=filter_bug_rate)
        self.faults = faults
        self._cursor = 0
        self._slot_of_doc: dict[int, int] = {}
        # monotone write counter (bumped once per ingest/update/delete call):
        # the front-door result cache keys warm-probing entries on it, so a
        # warm-tier write exactly invalidates the results it could change.
        self.commit_count = 0
        # host gap injected between the two write commits; models queue /
        # network / worker delay between the vector upsert and the metadata
        # upsert in a real deployment.
        self.write_gap_s = 0.0
        # optional lexical lanes (attach_lexical): slot-aligned postings for
        # the warm hybrid pushdown, sharing the corpus-global LexicalStats
        self.lex = None

    def attach_lexical(self, cfg, stats) -> None:
        """Grow slot-aligned postings lanes for hybrid pushdown queries.
        ``stats`` is the corpus-global `LexicalStats` shared with the hot
        arena, so idf/avgdl stay comparable across the tier merge."""
        from repro.index.lexical import LexicalArena
        self.lex = LexicalArena(self.cfg.capacity, cfg, stats)

    @property
    def n_docs(self) -> int:
        """LIVE rows (the planner skips the warm probe at 0)."""
        return len(self._slot_of_doc)

    def has_doc(self, doc_id: int) -> bool:
        return int(doc_id) in self._slot_of_doc

    def slot_of(self, doc_id: int) -> int:
        return self._slot_of_doc[int(doc_id)]

    def delete(self, doc_ids) -> list[int]:
        """Tombstone rows — TWO commits like every split-stack write (vector
        invalidate, then metadata), with the usual window in between,
        recorded in stats like every other write.
        Returns the freed slots (one per unique doc_id, in dedup order)."""
        slot_list = [self._slot_of_doc[d]
                     for d in dict.fromkeys(int(d) for d in doc_ids)]
        slots = jnp.asarray(slot_list, jnp.int32)
        t0 = time.perf_counter()
        self.valid = self.valid.at[slots].set(False)
        jax.block_until_ready(self.valid)
        t1 = time.perf_counter()
        if self.write_gap_s:
            time.sleep(self.write_gap_s)
        meta = dict(self.meta)
        meta["tenant"] = meta["tenant"].at[slots].set(-1)
        meta["doc_id"] = meta["doc_id"].at[slots].set(-1)
        self.meta = meta
        jax.block_until_ready(self.meta["tenant"])
        t2 = time.perf_counter()
        self.cache.invalidate(np.asarray(slots))
        self.stats.inconsistency_windows_s.append(t2 - t1)
        self.stats.write_latencies_s.append(t2 - t0)
        for d in doc_ids:
            self._slot_of_doc.pop(int(d), None)
        if self.lex is not None:     # postings leave with the row
            self.lex.clear_rows(slot_list)
        self.commit_count += 1
        return slot_list

    # -- writes: TWO separate commits -----------------------------------
    def ingest(self, batch: DocBatch) -> None:
        m = batch.size
        slots = jnp.arange(self._cursor, self._cursor + m, dtype=jnp.int32)
        t0 = time.perf_counter()
        # commit 1: vector store
        emb = normalize(self.cfg, batch.emb.astype(self.emb.dtype))
        self.emb, self.valid = vector_write(self.emb, self.valid, slots, emb)
        jax.block_until_ready(self.emb)
        t1 = time.perf_counter()
        if self.write_gap_s:
            time.sleep(self.write_gap_s)
        # commit 2: metadata store (a reader between t1 and t2 sees the new
        # vector with the OLD metadata — the inconsistency window)
        self.meta = metadata_write(self.meta, slots, batch.tenant, batch.category,
                                   batch.updated_at, batch.acl, batch.doc_id)
        jax.block_until_ready(self.meta["tenant"])
        t2 = time.perf_counter()
        self.cache.invalidate(np.asarray(slots))
        self.stats.inconsistency_windows_s.append(t2 - t1)
        self.stats.write_latencies_s.append(t2 - t0)
        for i, d in enumerate(jax.device_get(batch.doc_id)):
            self._slot_of_doc[int(d)] = self._cursor + i
        self._cursor += m
        if self.lex is not None:     # postings ride the metadata commit
            self.lex.write_rows(
                np.asarray(slots),
                None if batch.terms is None else np.asarray(batch.terms),
                None if batch.tfs is None else np.asarray(batch.tfs))
        self.commit_count += 1

    def update(self, doc_ids, new_emb, updated_at) -> None:
        slots = jnp.asarray([self._slot_of_doc[int(d)] for d in doc_ids], jnp.int32)
        t0 = time.perf_counter()
        emb = normalize(self.cfg, jnp.asarray(new_emb, self.emb.dtype))
        self.emb, self.valid = vector_write(self.emb, self.valid, slots, emb)
        jax.block_until_ready(self.emb)
        t1 = time.perf_counter()
        if self.write_gap_s:
            time.sleep(self.write_gap_s)
        meta = dict(self.meta)
        meta["updated_at"] = meta["updated_at"].at[slots].set(jnp.asarray(updated_at, jnp.int32))
        self.meta = meta
        jax.block_until_ready(self.meta["updated_at"])
        t2 = time.perf_counter()
        self.cache.invalidate(np.asarray(slots))
        self.stats.inconsistency_windows_s.append(t2 - t1)
        self.stats.write_latencies_s.append(t2 - t0)
        self.commit_count += 1

    # -- reads: vector search -> metadata fetch -> app-layer filter ------
    def _passes_filters(self, row: tuple, pred: Predicate, bug_active: bool) -> bool:
        tenant, category, updated_at, acl, doc_id = row
        if doc_id < 0:
            return False
        # THE BUG: under bug_active the tenant clause is skipped — exactly the
        # class of app-layer filter defect the paper measured at 0.2 %.
        if not bug_active and pred.tenant != -2 and tenant != pred.tenant:
            return False
        if updated_at < pred.min_ts:
            return False
        if not ((1 << int(category)) & pred.cat_mask):
            return False
        if not (int(acl) & pred.acl_bits):
            return False
        return True

    def query(self, q: jax.Array, pred: Predicate, k: int, *,
              pushdown: bool = False):
        """Returns (scores (B,k) np.float32, slots (B,k) np.int32).

        ``pushdown=False`` (Stack A as the paper measured it): vector scan,
        metadata fetch, app-layer post-filter, retry-on-underfill — every
        round trip counted, the injectable filter bug reachable.

        ``pushdown=True`` (the warm-tier route): the lowered predicate
        travels INTO the vector scan (`vector_topk_filtered`) — one round
        trip, exact fill, the app-layer filter (and its bug) out of the
        loop. The front-door executor always probes the warm tier this way.
        """
        if pushdown:
            # warm-tier fault sites: a stall (slow replica) and a hard error,
            # both scheduled by the attached FaultPlan — WarmGuard handles
            # retry/hedge/breaker above this layer.
            self.faults.stall("warm.stall")
            self.faults.raise_if("warm.error", WarmTierError)
            k_eff = min(k, self.cfg.capacity)
            s, i = vector_topk_filtered(self.emb, self.valid, self.meta, q,
                                        pred.as_array(), k_eff)
            self.stats.round_trips += 1
            s, i = np.asarray(s), np.asarray(i)
            if k_eff < k:
                pad = ((0, 0), (0, k - k_eff))
                s = np.pad(s, pad, constant_values=np.float32(
                    jax.device_get(NEG_INF)))
                i = np.pad(i, pad, constant_values=-1)
            return s, i
        B = q.shape[0]
        bug_active = self.faults.fires("split.filter_bug")
        fetch = k * self.OVERFETCH
        out_scores = np.full((B, k), np.float32(jax.device_get(NEG_INF)), np.float32)
        out_slots = np.full((B, k), -1, np.int32)
        for attempt in range(self.MAX_RETRIES + 1):
            # round trip 1..n: vector service
            scores, idx = vector_topk(self.emb, self.valid, q, min(fetch, self.cfg.capacity))
            scores, idx = jax.device_get((scores, idx))
            self.stats.round_trips += 1
            # metadata fetch: cache first, then the metadata service for misses
            uniq = np.unique(idx)
            missing = [s for s in uniq if self.cache.get(int(s)) is None]
            if missing:
                rows = jax.device_get(metadata_lookup(self.meta, jnp.asarray(missing, jnp.int32)))
                self.stats.round_trips += 1
                for j, s in enumerate(missing):
                    self.cache.put(int(s), (int(rows["tenant"][j]), int(rows["category"][j]),
                                            int(rows["updated_at"][j]), int(rows["acl"][j]),
                                            int(rows["doc_id"][j])))
            # app-layer post-filter + merge (the fragile part)
            done = True
            for b in range(B):
                kept = 0
                for j in range(idx.shape[1]):
                    s = int(idx[b, j])
                    row = self.cache.get(s)
                    if row is None:
                        continue
                    if self._passes_filters(row, pred, bug_active):
                        out_scores[b, kept] = scores[b, j]
                        out_slots[b, kept] = s
                        kept += 1
                        if kept == k:
                            break
                if kept < k and fetch < self.cfg.capacity:
                    done = False
            if done or fetch >= self.cfg.capacity:
                break
            fetch *= 4
            self.stats.retries += 1
        return out_scores, out_slots

    def query_hybrid(self, q, qterms, pred: Predicate, k: int, *,
                     mode: str = "wsum", w_dense: float = 1.0,
                     w_lex: float = 1.0, rrf_c: float = 60.0,
                     lists: bool = False):
        """Warm-tier hybrid probe with LEXICAL pushdown: predicate mask,
        dense scoring, and BM25 all run inside one scan (one round trip, no
        retries, no app-layer filter) — the hybrid twin of
        ``query(..., pushdown=True)``. ``qterms`` is (B, QT) int32 with -1
        padding. Returns (scores, slots) (B, k) numpy for "wsum"/fused rrf,
        or the four per-signal lists with ``lists=True`` (the tiered
        executor merges per signal before rank fusion)."""
        if self.lex is None:
            raise ValueError("warm tier has no lexical lanes — "
                             "attach_lexical() first")
        self.faults.stall("warm.stall")
        self.faults.raise_if("warm.error", WarmTierError)
        snap = self.lex.snapshot()
        k_eff = min(k, self.cfg.capacity)
        out = vector_topk_hybrid(self.emb, self.valid, self.meta,
                                 snap["terms"], snap["lexnorm"], snap["idf"],
                                 q, pred.as_array(),
                                 jnp.asarray(qterms, jnp.int32), k_eff,
                                 mode, float(w_dense), float(w_lex),
                                 float(rrf_c), lists)
        self.stats.round_trips += 1
        out = tuple(np.asarray(a) for a in out)
        if k_eff < k:
            pad = ((0, 0), (0, k - k_eff))
            neg = np.float32(jax.device_get(NEG_INF))
            out = tuple(np.pad(a, pad, constant_values=neg) if j % 2 == 0
                        else np.pad(a, pad, constant_values=-1)
                        for j, a in enumerate(out))
        return out
