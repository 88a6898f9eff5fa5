"""Chip smoke test: the served data-layer path, end to end, on a TPU.

    python chip_smoke.py                 # one chip: phases 1-6 below
    python chip_smoke.py --four-chips    # four chips: the row-sharded arena only

One chip. A 2^21 x 768 cosine arena (the `configs.rag_unified.PRODUCTION`
width; 6 GiB of a v5e's 16 GiB of HBM) with T=16 lexical lanes is filled
with 2,000,000 generated docs in the paper's corpus shapes
(`rag_unified.BENCH_CORPUS`: 20 tenants, 5 categories, 8 ACL groups) and
driven only through the entry points a user calls:

  1. store     `RagDB` ingest in equal chunks (peak HBM, ingest wall time);
  2. queries   the paper's four query levels (`SESSION_QUERIES`) through
               `db.session(...).search(q)...run()` for 8 tenants — the
               planner must pick the compiled `pallas` engine;
  3. scheduler 64 mixed-tenant requests through `serving.Scheduler`
               (fused grouped scans); every one served fresh or from cache;
  4. hybrid    `.match()` queries, wsum and rrf, on the `hybrid` engine;
  5. writes    ingest / update / delete, then read each write back and
               check the result cache never serves the pre-write answer;
  6. oracle    every returned row of phases 2-5 against a plain numpy
               float64 oracle over the same seeded chunks: tenant, ACL,
               category and recency hold for every row, and each top-k
               list equals the oracle's except where the oracle's k-th and
               (k+1)-th scores are within 1e-5 (a tie).

Four chips (`--four-chips`): the same per-chip size, 4 x 2^21 rows and
8,000,000 docs, in a `RagDB` on a 4-device mesh with tenant-affine
placement. Every lane must sit row-sharded on 4 devices; tenant-scoped and
unscoped queries run on the `sharded` engine against the same oracle, and
a tenant-scoped query may scan only its owning shard.

Progress goes to stdout. The LAST line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, when any phase raises, or when any check fails, the script
exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import SESSION_QUERIES  # noqa: E402
from repro.api import RagDB  # noqa: E402
from repro.configs.rag_unified import BENCH_CORPUS, PRODUCTION  # noqa: E402
from repro.core.query import Predicate, stack_predicates  # noqa: E402
from repro.core.store import DocBatch, StoreConfig  # noqa: E402
from repro.core.tenancy import Principal, category_mask  # noqa: E402
from repro.data.corpus import (DAY_S, chunk_columns,  # noqa: E402
                               make_keyword_queries, make_queries)
from repro.index.lexical import LexicalConfig  # noqa: E402
from repro.kernels.arena_scan.ops import arena_scan  # noqa: E402
from repro.kernels.arena_scan.stages import hybrid_spec  # noqa: E402
from repro.serving.scheduler import (Scheduler, SchedulerConfig,  # noqa: E402
                                     ServeRequest)

ALL = 0xFFFFFFFF
ANY = -2


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Sizes of one smoke run (the defaults are the one-chip run)."""
    capacity: int = 1 << 21
    dim: int = PRODUCTION.dim
    n_docs: int = 2_000_000
    chunk_rows: int = 62_500          # equal chunks: 32 x 62,500 = 2,000,000
    n_new: int = 4096
    n_update: int = 1024
    n_delete: int = 1024


FOUR_CHIPS = SmokeConfig(capacity=4 << 21, n_docs=8_000_000)

K = 10                 # LIMIT of every request
Q_ROWS = 8             # query rows per session request
TENANTS = 8            # tenants the tenant-scoped levels run for
SCHED_REQUESTS = 64    # requests through the serving scheduler
READBACK_ROWS = 8      # docs read back per tenant and kind of write
SEED = 0
GEN_THREADS = 8        # corpus-generation threads


class SmokeFailure(RuntimeError):
    """A smoke check failed."""


def require(ok, msg) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# the plain host oracle (numpy float64; shares no code with the engines)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Spec:
    """What one request asks, stated independently of the builder."""
    tenant: int = ANY
    acl_bits: int = ALL
    cats: tuple | None = None
    min_ts: int = 0
    terms: tuple | None = None        # match() term ids
    mode: str = "dense"               # "dense" | "wsum" | "rrf"

    @property
    def cat_mask(self) -> int:
        return ALL if self.cats is None else category_mask(self.cats)

    def predicate(self) -> Predicate:
        return Predicate(tenant=self.tenant, min_ts=self.min_ts,
                         cat_mask=self.cat_mask, acl_bits=self.acl_bits)


class HostOracle:
    """Host mirror of the corpus keyed by doc id, scored in float64 with
    the store's cosine normalisation, streamed in chunks."""

    def __init__(self, n_max: int, dim: int, lex: LexicalConfig | None, *,
                 tie_by_slot: bool):
        self.emb = np.zeros((n_max, dim), np.float32)
        self.inv_norm = np.zeros(n_max, np.float64)
        self.tenant = np.full(n_max, -1, np.int64)
        self.category = np.zeros(n_max, np.int64)
        self.updated_at = np.zeros(n_max, np.int64)
        self.acl = np.zeros(n_max, np.uint64)
        self.live = np.zeros(n_max, bool)
        self.lex = lex
        if lex is not None:
            self.terms = np.full((n_max, lex.doc_terms), -1, np.int64)
            self.tfs = np.zeros((n_max, lex.doc_terms), np.int64)
        # exact ties order as the engine orders them: by arena slot for the
        # single-device scans, by global doc id for the sharded engine
        self.tie_by_slot = tie_by_slot
        self.tiebreak = np.arange(n_max, dtype=np.int64)
        self.n = 0

    def sync_slots(self, slot_doc: np.ndarray) -> None:
        if self.tie_by_slot:
            used = slot_doc >= 0
            self.tiebreak[slot_doc[used]] = np.nonzero(used)[0]

    def _set_emb(self, ids, emb):
        self.emb[ids] = emb
        norm = np.linalg.norm(emb.astype(np.float64), axis=1)
        self.inv_norm[ids] = 1.0 / np.maximum(norm, 1e-12)

    def add(self, cols: dict) -> None:
        ids = np.asarray(cols["doc_id"], np.int64)
        self._set_emb(ids, cols["emb"])
        self.tenant[ids] = cols["tenant"]
        self.category[ids] = cols["category"]
        self.updated_at[ids] = cols["updated_at"]
        self.acl[ids] = cols["acl"]
        self.live[ids] = True
        if self.lex is not None:
            v, t_lanes = self.lex.vocab_size, self.lex.doc_terms
            t = np.asarray(cols["terms"], np.int64)[:, :t_lanes].copy()
            t[(t < 0) | (t >= v)] = -1
            for j in range(1, t.shape[1]):     # a term counts once per doc
                t[(t[:, :j] == t[:, j:j + 1]).any(axis=1), j] = -1
            f = np.asarray(cols["tfs"], np.int64)[:, :t_lanes]
            self.terms[ids, :t.shape[1]] = t
            self.tfs[ids, :t.shape[1]] = np.where(t >= 0, np.maximum(f, 1), 0)
        self.n = max(self.n, int(ids.max()) + 1)

    def update(self, ids, emb, ts) -> None:
        self._set_emb(ids, emb)
        self.updated_at[ids] = ts

    def delete(self, ids) -> None:
        self.live[ids] = False
        if self.lex is not None:
            self.terms[ids] = -1
            self.tfs[ids] = 0

    def mask(self, spec: Spec, lo: int, hi: int) -> np.ndarray:
        ok = self.live[lo:hi] & (self.tenant[lo:hi] >= 0)
        if spec.tenant != ANY:
            ok &= self.tenant[lo:hi] == spec.tenant
        ok &= self.updated_at[lo:hi] >= spec.min_ts
        ok &= ((np.uint64(spec.cat_mask)
                >> self.category[lo:hi].astype(np.uint64)) & np.uint64(1)) != 0
        ok &= (self.acl[lo:hi] & np.uint64(spec.acl_bits)) != 0
        return ok

    def _bm25_tables(self):
        """(idf over live docs, per-lane tf/length weight) in float64."""
        cfg = self.lex
        live = self.live[:self.n]
        t, f = self.terms[:self.n], self.tfs[:self.n]
        valid = (t >= 0) & live[:, None]
        df = np.bincount(t[valid], minlength=cfg.vocab_size)
        n_docs = int(valid.any(axis=1).sum())
        avgdl = f[valid].sum() / max(n_docs, 1)
        idf = np.maximum(np.log1p((n_docs - df + 0.5) / (df + 0.5)), 0.0)
        dl = f.sum(axis=1, keepdims=True).astype(np.float64)
        ln = f * (cfg.k1 + 1.0) / (f + cfg.k1 * (1.0 - cfg.b + cfg.b * dl
                                                 / max(avgdl, 1.0)))
        return idf, ln

    def lists(self, specs, qs, k: int, chunk: int = 1 << 16):
        """One float64 pass over the corpus. Returns, per spec, a dict of
        signal -> (top-(k+1) scores, doc ids) with -inf past the qualifying
        rows; ties order by the engine's own tie-break key."""
        q = np.asarray(qs, np.float64)
        q = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        signals = [("dense",) if s.mode == "dense" else
                   ("wsum",) if s.mode == "wsum" else ("dense", "lex")
                   for s in specs]
        if any(s.terms for s in specs):
            idf, ln = self._bm25_tables()
        best = [{sig: (np.full(0, -np.inf), np.zeros(0, np.int64))
                 for sig in sigs} for sigs in signals]
        for lo in range(0, self.n, chunk):
            hi = min(lo + chunk, self.n)
            dense = (self.emb[lo:hi].astype(np.float64) @ q.T
                     * self.inv_norm[lo:hi, None])
            masks = {}
            for b, spec in enumerate(specs):
                key = spec.predicate()
                if key not in masks:
                    masks[key] = self.mask(spec, lo, hi)
                m = masks[key]
                if spec.terms:
                    bm = np.zeros(hi - lo)
                    for term in spec.terms:
                        hit = self.terms[lo:hi] == term
                        bm += idf[term] * (hit * ln[lo:hi]).sum(axis=1)
                for sig in signals[b]:
                    s = (dense[:, b] if sig == "dense" else
                         bm if sig == "lex" else dense[:, b] + bm)
                    s = np.where(m, s, -np.inf)
                    top = np.argpartition(-s, min(k, len(s) - 1))[:k + 1]
                    old_s, old_i = best[b][sig]
                    best[b][sig] = self._cut(np.concatenate([old_s, s[top]]),
                                             np.concatenate([old_i, top + lo]),
                                             k + 1)
        return best

    def _cut(self, s, ids, n):
        order = np.lexsort((self.tiebreak[ids], -s))[:n]
        return s[order], ids[order]

    def score(self, spec: Spec, q, doc: int, idf=None, ln=None) -> float:
        """Float64 score of one doc under one request's signal."""
        qn = np.asarray(q, np.float64)
        qn = qn / max(np.linalg.norm(qn), 1e-12)
        s = float(self.emb[doc].astype(np.float64) @ qn * self.inv_norm[doc])
        if spec.mode == "wsum":
            hits = np.isin(self.terms[doc], spec.terms)
            s += float(np.sum(idf[self.terms[doc][hits]] * ln[doc][hits]))
        return s


class Checker:
    """Accumulates isolation violations, oracle mismatches and ties."""

    def __init__(self, oracle: HostOracle, k: int):
        self.o = oracle
        self.k = k
        self.rows = self.violations = self.mismatches = self.ties = 0
        self.notes: list[str] = []

    def _tol(self, s: float) -> float:
        return 1e-5 * max(1.0, abs(s))

    def isolation(self, spec: Spec, doc_ids) -> None:
        for d in doc_ids:
            if d < 0:
                continue
            if not self.o.mask(spec, int(d), int(d) + 1)[0]:
                self.violations += 1
                self.notes.append(f"violation: doc {d} under {spec}")

    def _list(self, dev, o_s, o_i, score_of):
        """'ok' | 'tie' | 'mismatch' for one ranked list."""
        k = self.k
        n_q = int(np.isfinite(o_s).sum())
        want = o_i[:min(k, n_q)]
        got = dev[dev >= 0]
        tied = n_q > k and o_s[k - 1] - o_s[k] <= self._tol(o_s[k - 1])
        same = set(got.tolist()) == set(want.tolist())
        if same and len(got) == len(want):
            for i, d in enumerate(got):
                if abs(score_of(int(d)) - o_s[i]) > self._tol(o_s[i]):
                    return "tie" if tied else "mismatch"
            return "ok"
        return "tie" if tied else "mismatch"

    def check(self, spec: Spec, q, dev_ids, best, tables=None) -> None:
        """One request row: isolation on every returned row, then the list
        against the oracle."""
        self.rows += 1
        self.isolation(spec, dev_ids)
        idf, ln = tables if tables is not None else (None, None)
        if spec.mode == "rrf":
            if self._ranks_tied(best["dense"][0]) or self._ranks_tied(
                    best["lex"][0]):
                verdict = "tie"     # fused ranks of a tied list: excused
            else:
                fused = rrf_oracle(best["dense"][1][:self.k],
                                   best["lex"][1][:self.k], self.k,
                                   self.o.lex.rrf_c,
                                   np.isfinite(best["dense"][0][:self.k]),
                                   np.isfinite(best["lex"][0][:self.k]))
                verdict = ("ok" if np.array_equal(np.asarray(dev_ids), fused)
                           else "mismatch")
        else:
            sig = "wsum" if spec.mode == "wsum" else "dense"
            o_s, o_i = best[sig]
            verdict = self._list(np.asarray(dev_ids), o_s, o_i,
                                 lambda d: self.o.score(spec, q, d, idf, ln))
        if verdict == "tie":
            self.ties += 1
        elif verdict == "mismatch":
            self.mismatches += 1
            self.notes.append(f"mismatch: {spec} got {list(dev_ids)} "
                              f"want {best}")

    def _ranks_tied(self, o_s) -> bool:
        """Whether the ranks of one oracle per-signal list are undetermined
        at f32 precision: a tie at the k-th place, or two listed scores
        within rounding of each other without being equal. (An rrf request
        returns only the fused list, which depends on those ranks.)"""
        k = self.k
        fin = o_s[np.isfinite(o_s)]
        if len(fin) > k and o_s[k - 1] - o_s[k] <= self._tol(o_s[k - 1]):
            return True
        gaps = fin[:k][:-1] - fin[:k][1:]
        return bool(len(fin) and ((gaps > 0)
                                  & (gaps <= self._tol(float(fin[0])))).any())

    def summary(self) -> str:
        return (f"{self.rows} request rows checked: "
                f"{self.violations} isolation violations, "
                f"{self.mismatches} untied mismatches, "
                f"{self.ties} tie-excused")


def rrf_oracle(d_ids, l_ids, k, c, d_valid, l_valid) -> np.ndarray:
    """Reciprocal-rank fusion of two ranked id lists: score = sum over the
    lists holding a doc of 1/(c + rank); a doc in both keeps its dense
    position; ties go to the earlier position in [dense | lex]."""
    cand = []
    for r, d in enumerate(d_ids):
        if d_valid[r]:
            s = 1.0 / (c + r + 1)
            both = np.nonzero((l_ids == d) & l_valid)[0]
            if len(both):
                s += 1.0 / (c + both[0] + 1)
            cand.append((np.float32(s), len(cand), d))
    for r, d in enumerate(l_ids):
        if l_valid[r] and not (d_valid & (d_ids == d)).any():
            cand.append((np.float32(1.0 / (c + r + 1)), k + r, d))
    cand.sort(key=lambda x: (-x[0], x[1]))
    out = np.full(k, -1, np.int64)
    for i, (_, _, d) in enumerate(cand[:k]):
        out[i] = d
    return out


# ---------------------------------------------------------------------------
# store build
# ---------------------------------------------------------------------------

def corpus_cfg(cfg: SmokeConfig):
    return dataclasses.replace(BENCH_CORPUS, n_docs=cfg.n_docs, dim=cfg.dim,
                               seed=SEED)


def ingest_corpus(db: RagDB, oracle: HostOracle, cfg: SmokeConfig) -> dict:
    """Stream the seeded corpus into ``db`` and the oracle; chunk c+1..
    generate on worker threads while chunk c is ingested."""
    ccfg = corpus_cfg(cfg)
    n_chunks = -(-cfg.n_docs // cfg.chunk_rows)
    first = None
    t0 = time.perf_counter()
    with ThreadPoolExecutor(GEN_THREADS) as ex:
        futs: deque = deque()
        for c in range(n_chunks):
            while len(futs) < GEN_THREADS and c + len(futs) < n_chunks:
                futs.append(ex.submit(chunk_columns, ccfg, c + len(futs),
                                      cfg.chunk_rows))
            cols = futs.popleft().result()
            if first is None:
                first = cols
            oracle.add(cols)
            db.ingest(DocBatch(**{name: jnp.asarray(col)
                                  for name, col in cols.items()}))
    jax.block_until_ready(db.log.snapshot()["emb"])
    return {"ingest_s": time.perf_counter() - t0, "chunks": n_chunks,
            "first_chunk": first}


def slot_docs(db: RagDB) -> np.ndarray:
    return np.asarray(jax.device_get(db.log.snapshot()["doc_id"]))


def peak_hbm() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

LEVEL_SPECS = {
    "pure_similarity": lambda ccfg, t: Spec(),
    "date_filter": lambda ccfg, t: Spec(min_ts=ccfg.now_ts - 60 * DAY_S),
    "tenant_category": lambda ccfg, t: Spec(tenant=t, cats=(1, 2)),
    "full_multi": lambda ccfg, t: Spec(tenant=t, acl_bits=0b0011, cats=(1, 2),
                                       min_ts=ccfg.now_ts - 60 * DAY_S),
}


def doc_ids_of(checker, slot_doc, spec, slots) -> np.ndarray:
    """Arena slots -> doc ids; a returned slot holding no doc (a freed
    row) is an isolation violation."""
    ids = np.where(slots >= 0, slot_doc[np.maximum(slots, 0)], -1)
    freed = int(((slots >= 0) & (ids < 0)).sum())
    if freed:
        checker.violations += freed
        checker.notes.append(f"violation: freed slot returned under {spec}")
    return ids


def run_and_check(db, builders, specs, qs, checker, engine, *, k,
                  tables=None):
    """Run each builder through `.run()`, assert its engine, and check
    every returned row against the oracle. Returns the results."""
    slot_doc = slot_docs(db)
    checker.o.sync_slots(slot_doc)
    rows = [(s, q) for s, qb in zip(specs, qs) for q in np.atleast_2d(qb)]
    best = checker.o.lists([s for s, _ in rows], [q for _, q in rows], k)
    out, r = [], 0
    for b, spec, qb in zip(builders, specs, qs):
        res = b.run()
        require(res.plan.engine == engine,
                (res.plan.engine, res.plan.explain()))
        for i, q in enumerate(np.atleast_2d(qb)):
            ids = doc_ids_of(checker, slot_doc, spec, res.slots[i])
            checker.check(spec, q, ids, best[r], tables)
            r += 1
        out.append(res)
    return out


def phase_queries(db, oracle, checker, cfg, ccfg, report):
    """Phase 2: the four paper query levels through the front door."""
    rng_seed = SEED + 100
    builders, specs, qs = [], [], []
    for li, (level, make) in enumerate(SESSION_QUERIES.items()):
        for t in range(TENANTS):
            q = np.asarray(make_queries(ccfg, 1, batch=Q_ROWS,
                                        seed=rng_seed + 31 * li + t))[0]
            b = make(db, ccfg, q, tenant=t).limit(K)
            spec = LEVEL_SPECS[level](ccfg, t)
            require(b.plan().pred == spec.predicate(), (level, b.plan().pred))
            builders.append(b)
            specs.append(spec)
            qs.append(q)
    t0 = time.perf_counter()
    first = builders[0].run()
    report["first_query_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    builders[1].run()
    report["second_query_s"] = time.perf_counter() - t0
    require(first.plan.engine == "pallas", first.plan.explain())
    say(f"phase 2: first query {report['first_query_s']:.3f}s (compile "
        f"included), second {report['second_query_s']:.3f}s; plan:\n"
        + first.plan.explain())
    run_and_check(db, builders, specs, qs, checker, "pallas", k=K)
    # the same batch on the ref engine: are the bits identical?
    b = builders[-1]
    pal = b.run()
    ref = b.using("ref").run()
    require(ref.plan.engine == "ref", ref.plan.explain())
    report["ref_pallas_identical"] = bool(
        np.array_equal(pal.scores, ref.scores)
        and np.array_equal(pal.slots, ref.slots))
    report["ref_pallas_max_abs_diff"] = float(
        np.max(np.abs(pal.scores - ref.scores)))
    say(f"phase 2: {len(builders)} session requests x {Q_ROWS} rows on "
        f"pallas; ref vs pallas identical bits: "
        f"{report['ref_pallas_identical']} (max |diff| "
        f"{report['ref_pallas_max_abs_diff']:.3g}); {checker.summary()}")


def phase_scheduler(db, oracle, checker, cfg, ccfg, report):
    """Phase 3: mixed-tenant requests through the serving scheduler."""
    sched = Scheduler(db, SchedulerConfig(slo_ms=60_000.0,
                                          max_queue=2 * SCHED_REQUESTS))
    q = np.asarray(make_queries(ccfg, SCHED_REQUESTS, seed=SEED + 7)
                   )[:, 0]
    fused0 = db.stats.fused_scans
    specs, reqs = [], []
    for i in range(SCHED_REQUESTS):
        t = i % TENANTS
        level = ("tenant_category", "full_multi")[(i // TENANTS) % 2]
        spec = LEVEL_SPECS[level](ccfg, t)
        plan = SESSION_QUERIES[level](db, ccfg, q[i], tenant=t).limit(
            K).plan()
        require(plan.engine == "pallas", plan.explain())
        req = ServeRequest(plan=plan, arrival_t=sched.clock(), req_id=i,
                           tenant=t)
        require(sched.offer(req), "scheduler shed a request")
        specs.append(spec)
        reqs.append(req)
    results = sched.run_until_idle()
    require(len(results) == SCHED_REQUESTS, len(results))
    served = [r.served for r in results]
    require(set(served) <= {"fresh", "cache"}, served)
    slot_doc = slot_docs(db)
    oracle.sync_slots(slot_doc)
    best = oracle.lists(specs, q, K)
    by_id = {r.request.req_id: r for r in results}
    for i, spec in enumerate(specs):
        ids = doc_ids_of(checker, slot_doc, spec, by_id[i].slots[0])
        checker.check(spec, q[i], ids, best[i])
    report["sched_fused_scans"] = db.stats.fused_scans - fused0
    report["sched_served"] = {s: served.count(s) for s in set(served)}
    say(f"phase 3: {len(results)} scheduled requests served "
        f"{report['sched_served']}, {report['sched_fused_scans']} fused "
        f"grouped scans; {checker.summary()}")


def phase_hybrid(db, oracle, checker, cfg, ccfg, report, first_chunk):
    """Phase 4: match() queries on the hybrid engine, wsum and rrf."""
    corpus = DocBatch(**first_chunk)
    qv, terms, _ = make_keyword_queries(ccfg, corpus, 4, seed=SEED + 3)
    anchor_tenant = [int(corpus.tenant[np.nonzero(
        (np.asarray(corpus.terms) == t[0]).any(axis=1))[0][0]]) for t in terms]
    builders, specs, qs = [], [], []
    for mode in ("wsum", "rrf"):
        for i in range(len(qv)):
            for scoped in (False, True):
                t = anchor_tenant[i] if scoped else ANY
                sess = (db.session(Principal(tenant_id=t, group_bits=ALL))
                        if scoped else db.admin_session())
                builders.append(sess.search(qv[i]).match(terms[i])
                                .fuse(mode).limit(K))
                specs.append(Spec(tenant=t, terms=tuple(terms[i]), mode=mode))
                qs.append(qv[i][None, :])
    tables = oracle._bm25_tables()
    run_and_check(db, builders, specs, qs, checker, "hybrid", k=K,
                  tables=tables)
    say(f"phase 4: {len(builders)} match() requests (wsum + rrf) on hybrid; "
        f"{checker.summary()}")


def phase_writes(db, oracle, checker, cfg, ccfg, report):
    """Phase 5: ingest, update and delete, then read every write back."""
    rng = np.random.default_rng(SEED + 5)
    n0 = oracle.n
    live_ids = np.nonzero(oracle.live[:n0])[0]
    touched = rng.choice(live_ids, cfg.n_update + cfg.n_delete, replace=False)
    upd_ids, del_ids = touched[:cfg.n_update], touched[cfg.n_update:]

    def per_tenant(ids, n):
        """Up to n ids for each of the first ``TENANTS`` tenants."""
        out = {}
        for t in range(TENANTS):
            own = ids[oracle.tenant[ids] == t][:n]
            if len(own):
                out[t] = own
        return out

    def own_queries(groups, emb_of):
        builders, specs, qs = [], [], []
        for t, ids in groups.items():
            q = emb_of(ids)
            builders.append(db.session(Principal(tenant_id=t, group_bits=ALL))
                            .search(q).limit(K))
            specs.append(Spec(tenant=t))
            qs.append(q)
        return builders, specs, qs

    # the pre-write answer, cached: deleted docs' own embeddings
    del_groups = per_tenant(del_ids, READBACK_ROWS)
    pre_b, _, _ = own_queries(del_groups, lambda ids: oracle.emb[ids].copy())
    for b in pre_b:
        b.run()
    require(all(b.run().cached for b in pre_b), "pre-write query not cached")

    # writes: new docs, re-embedded docs, deleted docs
    new_cfg = dataclasses.replace(corpus_cfg(cfg), n_docs=cfg.n_new,
                                  seed=SEED + 1)
    cols = chunk_columns(new_cfg, 0, cfg.n_new)
    cols["doc_id"] = cols["doc_id"] + n0
    oracle.add(cols)
    db.ingest(DocBatch(**{name: jnp.asarray(c) for name, c in cols.items()}))
    upd_emb = np.asarray(make_queries(ccfg, 1, batch=cfg.n_update,
                                      seed=SEED + 9))[0]
    now = ccfg.now_ts
    db.update(upd_ids, upd_emb, np.full(cfg.n_update, now, np.int32))
    oracle.update(upd_ids, upd_emb, now)
    db.delete(del_ids)
    oracle.delete(del_ids)

    # read back: first the cached pre-write queries — a hit now would be
    # the pre-write answer served from the result cache
    slot_doc = slot_docs(db)
    fails: list[str] = []

    def top_docs(res):
        return np.where(res.slots >= 0, slot_doc[np.maximum(res.slots, 0)], -1)

    for b in pre_b:
        again = b.run()
        if again.cached:
            fails.append("result cache served a pre-write answer")
        if np.isin(del_ids, top_docs(again)).any():
            fails.append("a deleted doc came back after the write")
    new_ids = cols["doc_id"]
    upd_pos = {int(d): i for i, d in enumerate(upd_ids)}
    checks = {"new first": 0, "updated first": 0, "deleted absent": 0}

    for name, groups, emb_of in (
            ("new first", per_tenant(new_ids, READBACK_ROWS),
             lambda ids: oracle.emb[ids].copy()),
            ("updated first", per_tenant(upd_ids, READBACK_ROWS),
             lambda ids: upd_emb[[upd_pos[int(d)] for d in ids]]),
            ("deleted absent", del_groups,
             lambda ids: oracle.emb[ids].copy())):
        builders, specs, qs = own_queries(groups, emb_of)
        results = run_and_check(db, builders, specs, qs, checker, "pallas",
                                k=K)
        for (t, ids), res in zip(groups.items(), results):
            docs = top_docs(res)
            for i, d in enumerate(ids):
                if name == "deleted absent":
                    ok = not np.isin(del_ids, docs).any()
                else:
                    ok = docs[i, 0] == d and res.scores[i, 0] > 1 - 1e-4
                checks[name] += int(ok)
                if not ok:
                    fails.append(f"{name}: doc {d} tenant {t} got {docs[i]}")
    report["readback"] = checks
    report["readback_failures"] = fails
    say(f"phase 5: +{cfg.n_new} new, {cfg.n_update} updated, "
        f"{cfg.n_delete} deleted; read back {checks}; failures "
        f"{len(fails)}; "
        f"{checker.summary()}")
    require(not fails, fails[:5])


def hot_program_text(db, cfg) -> dict:
    """The compiled text of the pallas and hybrid hot programs on the live
    arena (a TPU build holds `tpu_custom_call`)."""
    snap = db.log.snapshot()
    lex = db.lex.snapshot()
    q = jax.ShapeDtypeStruct((Q_ROWS, cfg.dim), jnp.float32)
    qt = jax.ShapeDtypeStruct((Q_ROWS, 1), jnp.int32)
    preds = stack_predicates([Predicate(tenant=0)])
    gids = jnp.zeros((Q_ROWS,), jnp.int32)
    pallas = jax.jit(lambda st, q, p: arena_scan(
        q, st["emb"], st["tenant"], st["updated_at"], st["category"],
        st["acl"], gids, p, K, use_kernel=True))
    hybrid = jax.jit(lambda st, lx, q, qt, p: arena_scan(
        q, st["emb"], st["tenant"], st["updated_at"], st["category"],
        st["acl"], gids, p, K, spec=hybrid_spec("wsum"), terms=lx["terms"],
        lexnorm=lx["lexnorm"], idf=lx["idf"], qterms=qt))
    return {"pallas": pallas.lower(snap, q, preds).compile().as_text(),
            "hybrid": hybrid.lower(snap, lex, q, qt, preds).compile().as_text()}


def run_one_chip(cfg: SmokeConfig = SmokeConfig()) -> dict:
    """Phases 1-6 (see module docstring). Returns the report; raises on any
    failed check."""
    report: dict = {}
    ccfg = corpus_cfg(cfg)
    lex_cfg = LexicalConfig()
    db = RagDB(StoreConfig(capacity=cfg.capacity, dim=cfg.dim,
                           metric="cosine"),
               lexical_cfg=lex_cfg)
    oracle = HostOracle(cfg.n_docs + cfg.n_new, cfg.dim, lex_cfg,
                        tie_by_slot=True)
    built = ingest_corpus(db, oracle, cfg)
    report.update(arena_rows=cfg.capacity, dim=cfg.dim, docs=cfg.n_docs,
                  ingest_s=built["ingest_s"], peak_hbm_after_ingest=peak_hbm())
    say(f"phase 1: arena {cfg.capacity} x {cfg.dim} f32 "
        f"({cfg.capacity * cfg.dim * 4 / 2**30:.2f} GiB), {cfg.n_docs} docs "
        f"in {built['chunks']} chunks of {cfg.chunk_rows}, ingest "
        f"{built['ingest_s']:.1f}s wall, peak_bytes_in_use "
        f"{report['peak_hbm_after_ingest']}")
    checker = Checker(oracle, K)
    phase_queries(db, oracle, checker, cfg, ccfg, report)
    phase_scheduler(db, oracle, checker, cfg, ccfg, report)
    phase_hybrid(db, oracle, checker, cfg, ccfg, report, built["first_chunk"])
    phase_writes(db, oracle, checker, cfg, ccfg, report)
    texts = hot_program_text(db, cfg)
    report["tpu_custom_call"] = {name: "tpu_custom_call" in txt
                                 for name, txt in texts.items()}
    report.update(rows_checked=checker.rows, violations=checker.violations,
                  mismatches=checker.mismatches, ties=checker.ties,
                  peak_hbm=peak_hbm())
    say(f"phase 6: {checker.summary()}; compiled hot programs hold "
        f"tpu_custom_call: {report['tpu_custom_call']}; peak_bytes_in_use "
        f"{report['peak_hbm']}")
    for note in checker.notes[:10]:
        say(note)
    require(checker.violations == 0, "isolation violated")
    require(checker.mismatches == 0, "results differ from the oracle")
    return report


def run_four_chips(cfg: SmokeConfig = FOUR_CHIPS) -> dict:
    """The row-sharded arena on a 4-device mesh, tenant-affine placement."""
    from repro.launch.mesh import make_mesh
    report: dict = {}
    ccfg = corpus_cfg(cfg)
    n_dev = 4
    mesh = make_mesh((n_dev,), ("data",))
    db = RagDB(StoreConfig(capacity=cfg.capacity, dim=cfg.dim,
                           metric="cosine"),
               mesh=mesh, placement="tenant",
               result_cache_size=0)
    oracle = HostOracle(cfg.n_docs, cfg.dim, None, tie_by_slot=False)
    built = ingest_corpus(db, oracle, cfg)
    snap = db.log.snapshot()
    rows = cfg.capacity // n_dev
    for name, arr in snap.items():
        if arr.ndim == 0:
            continue
        shards = arr.addressable_shards
        devs = {s.device for s in shards}
        require(len(devs) == n_dev, (name, devs))
        require(all(s.data.shape[0] == rows for s in shards),
                (name, [s.data.shape for s in shards]))
    say(f"four chips: arena {cfg.capacity} x {cfg.dim} f32, {cfg.n_docs} docs "
        f"ingested in {built['ingest_s']:.1f}s; every lane row-sharded on "
        f"{n_dev} devices at {rows} rows each; peak_bytes_in_use (device 0) "
        f"{peak_hbm()}")
    report.update(ingest_s=built["ingest_s"], rows_per_shard=rows)
    checker = Checker(oracle, K)
    builders, specs, qs = [], [], []
    for li, level in enumerate(SESSION_QUERIES):
        for t in range(TENANTS if LEVEL_SPECS[level](ccfg, 0).tenant
                       != ANY else 1):
            q = np.asarray(make_queries(ccfg, 1, batch=Q_ROWS,
                                        seed=SEED + 200 + 31 * li + t))[0]
            builders.append(SESSION_QUERIES[level](db, ccfg, q, tenant=t)
                            .limit(K))
            specs.append(LEVEL_SPECS[level](ccfg, t))
            qs.append(q)
    t0 = time.perf_counter()
    run_and_check(db, builders, specs, qs, checker, "sharded", k=K)
    say(f"four chips: {len(builders)} requests on sharded in "
        f"{time.perf_counter() - t0:.1f}s (compile included); "
        f"{checker.summary()}")
    skipped = 0
    for b, spec in zip(builders, specs):
        if spec.tenant == ANY:
            continue
        before = list(db.stats.shard_rows_scanned)
        b.run()
        after = db.stats.shard_rows_scanned
        delta = [a - (before[i] if i < len(before) else 0)
                 for i, a in enumerate(after)]
        owner = spec.tenant % n_dev
        require(delta[owner] == rows and sum(delta) == rows, (spec, delta))
        skipped += n_dev - 1
    report.update(rows_checked=checker.rows, violations=checker.violations,
                  mismatches=checker.mismatches, ties=checker.ties,
                  shard_scans_skipped=skipped)
    say(f"four chips: tenant-scoped queries scanned only the owning shard "
        f"({skipped} shard scans skipped); {checker.summary()}")
    for note in checker.notes[:10]:
        say(note)
    require(checker.violations == 0, "isolation violated")
    require(checker.mismatches == 0, "results differ from the oracle")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-sharded arena on 4 chips")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        say(f"no TPU: JAX found {dev.platform} ({dev.device_kind})")
        return 1
    from repro.runtime import configure_compile_cache
    say(f"compile cache: {configure_compile_cache()}")
    say(f"device: {dev.device_kind} x {jax.device_count()}")
    if args.four_chips:
        if jax.device_count() < 4:
            say(f"--four-chips needs 4 chips, found {jax.device_count()}")
            return 1
        run_four_chips()
    else:
        report = run_one_chip()
        require(all(report["tpu_custom_call"].values()),
                "a hot program compiled without its Pallas kernel")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
