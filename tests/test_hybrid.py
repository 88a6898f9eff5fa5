"""The hybrid dense+BM25 engine: one arena pass, both signals, same arena.

Acceptance contracts (ISSUE 5):
  * the hybrid arena-scan kernel (interpret mode) is BIT-identical to the
    jnp dense oracle AND the jnp streaming scan — across query-term counts
    {1, 4, T_max} and both fusion modes;
  * LEXICAL-PATH LEAKAGE IMPOSSIBILITY: a row outside the predicate group
    can never surface no matter how high its BM25 score — attacked on a
    seed grid with adversarial donor docs that match the query terms
    perfectly but belong to another tenant / ACL group;
  * hybrid recall@10 beats dense-only recall@10 on the keyword-anchored
    query grid (the workload the subsystem exists for);
  * the result cache stays snapshot-exact across LEXICAL writes: postings
    ride the same commit counters, and corpus-stat drift (idf/avgdl) keys
    the entry via the LexicalStats version;
  * the planner only ever picks "hybrid" for match() queries: no clause ->
    dense engines, clause -> hybrid, conflicting hints -> refused.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RagDB
from repro.api.planner import CostModel, PlannerConfig, choose_engine
from repro.api.plan import LogicalPlan
from repro.core import Predicate, Principal, StoreConfig
from repro.core.query import stack_predicates
from repro.core.store import DocBatch
from repro.data.corpus import (DAY_S, CorpusConfig, make_corpus,
                               make_keyword_queries)
from repro.index.lexical import LexicalArena, LexicalConfig
from repro.index.lexical.twoscan import two_scan_hybrid
from repro.kernels.arena_scan.ops import _packed_meta, arena_scan
from repro.kernels.arena_scan.stages import hybrid_spec
from tests.test_arena_scan_conformance import hybrid_oracle

pytestmark = [pytest.mark.kernels, pytest.mark.slow]

T_MAX = 16   # LexicalConfig.max_query_terms default


def _arena(rng, n, d=16, v=64, t_lanes=6, n_tenants=5):
    terms = rng.integers(-1, v, (n, t_lanes)).astype(np.int32)
    lexnorm = np.where(terms >= 0,
                       (rng.random((n, t_lanes)) * 2).astype(np.float32),
                       0.0).astype(np.float32)
    return {
        "emb": jnp.asarray(rng.standard_normal((n, d)).astype(np.float32)),
        "tenant": jnp.asarray(rng.integers(-1, n_tenants, n, dtype=np.int32)),
        "updated_at": jnp.asarray(rng.integers(0, 1000, n, dtype=np.int32)),
        "category": jnp.asarray(rng.integers(0, 8, n, dtype=np.int32)),
        "acl": jnp.asarray(rng.integers(1, 16, n, dtype=np.int64)
                           .astype(np.uint32)),
        "terms": jnp.asarray(terms),
        "lexnorm": jnp.asarray(lexnorm),
        "idf": jnp.asarray((rng.random(v) * 5).astype(np.float32)),
    }


def _call(store, q, gids, preds, qterms, k, mode, **kw):
    return arena_scan(q, store["emb"], store["tenant"], store["updated_at"],
                      store["category"], store["acl"], gids, preds, k,
                      spec=hybrid_spec(mode), terms=store["terms"],
                      lexnorm=store["lexnorm"], idf=store["idf"],
                      qterms=qterms, **kw)


# ---------------------------------------------------------------------------
# kernel / dense oracle / streaming scan bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["wsum", "rrf"])
@pytest.mark.parametrize("qt", [1, 4, T_MAX])
@pytest.mark.parametrize("B,N,D,k,blk_n", [
    (5, 700, 48, 8, 256),      # N not a block multiple -> padding path
    (8, 1024, 128, 10, 512),
    (1, 64, 8, 4, 64),         # tiny arena, B=1
])
def test_kernel_bit_identical_to_refs(mode, qt, B, N, D, k, blk_n, rng):
    """Pallas kernel body (interpret mode on CPU) vs jnp dense oracle vs jnp
    streaming scan: every score and slot bit-equal, for every query-term
    count and both fusion modes."""
    G = 3
    store = _arena(rng, N, D)
    q = rng.standard_normal((B, D)).astype(np.float32)
    qterms = rng.integers(-1, 64, (B, qt)).astype(np.int32)
    qterms[:, 0] = rng.integers(0, 64, B)        # at least one real term
    gids = rng.integers(0, G, B).astype(np.int32)
    preds = stack_predicates(
        [Predicate(tenant=i % 3, min_ts=100) for i in range(G)])
    kw = dict(w_dense=0.8, w_lex=1.7)
    s_r, i_r = _call(store, q, gids, preds, qterms, k, mode,
                     use_kernel=False, blk_n=blk_n, **kw)
    s_k, i_k = _call(store, q, gids, preds, qterms, k, mode,
                     use_kernel=True, interpret=True, blk_n=blk_n, **kw)
    assert (np.asarray(s_r) == np.asarray(s_k)).all()
    assert (np.asarray(i_r) == np.asarray(i_k)).all()
    # dense oracle (un-tiled) agrees too
    meta = _packed_meta(store["tenant"], store["updated_at"],
                        store["category"], store["acl"])
    qidf = np.where(qterms >= 0,
                    np.asarray(store["idf"])[np.clip(qterms, 0, None)],
                    0.0).astype(np.float32)
    s_o, i_o = hybrid_oracle(jnp.asarray(q), store["emb"], meta,
                             store["terms"].T, store["lexnorm"].T,
                             jnp.asarray(gids), preds, jnp.asarray(qterms),
                             jnp.asarray(qidf), k, mode=mode, **kw)
    assert (np.asarray(s_r) == np.asarray(s_o)).all()
    assert (np.asarray(i_r) == np.asarray(i_o)).all()


# ---------------------------------------------------------------------------
# lexical-path leakage impossibility (seed grid, adversarial)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(12))
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("mode", ["wsum", "rrf"])
def test_lexical_leakage_impossible(seed, use_kernel, mode):
    """Adversarial donors: rows in ANOTHER tenant (or outside the ACL)
    carry EXACTLY the query's terms at maximal weight — the highest BM25
    score in the arena. They must never surface: the predicate mask lands
    on the lexical signal before any ranking."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(80, 300))
    d, v, t_lanes, k = 8, 32, 4, 12
    q_terms_row = rng.integers(0, v, 3).astype(np.int32)
    store = _arena(rng, n, d, v, t_lanes)
    tenant = np.asarray(store["tenant"]).copy()
    terms = np.asarray(store["terms"]).copy()
    lexnorm = np.asarray(store["lexnorm"]).copy()
    # half the rows become donors: other tenant, perfect term match, huge tf
    donors = rng.random(n) < 0.5
    tenant[donors] = 3
    terms[donors, :3] = q_terms_row
    lexnorm[donors, :3] = 10.0
    store["tenant"] = jnp.asarray(tenant)
    store["terms"] = jnp.asarray(terms)
    store["lexnorm"] = jnp.asarray(lexnorm)
    pred = Predicate(tenant=1, acl_bits=int(rng.integers(1, 16)))
    B = 4
    q = rng.standard_normal((B, d)).astype(np.float32)
    qterms = np.tile(q_terms_row, (B, 1)).astype(np.int32)
    s, slots = _call(store, q, np.zeros(B, np.int32),
                     stack_predicates([pred]), qterms, k, mode,
                     use_kernel=use_kernel,
                     interpret=use_kernel or None, blk_n=64)
    slots = np.asarray(slots)
    acl = np.asarray(store["acl"])
    ts = np.asarray(store["updated_at"])
    ok = (tenant == 1) & (acl & pred.acl_bits != 0) & (ts >= pred.min_ts)
    for b in range(B):
        got = slots[b][slots[b] >= 0]
        assert ok[got].all(), (
            f"LEAK: a row outside the predicate group surfaced on the "
            f"lexical path (seed {seed}, row {b})")
        assert len(got) == min(k, int(ok.sum()))   # and no under-fill


# ---------------------------------------------------------------------------
# keyword-anchored recall: hybrid must beat dense-only
# ---------------------------------------------------------------------------

def _keyword_db(seed, n_docs=2500, dim=32):
    ccfg = CorpusConfig(n_docs=n_docs, dim=dim, seed=seed, vocab_size=512,
                        n_topics=16, n_entity_terms=64, entity_frac=0.06)
    db = RagDB(StoreConfig(capacity=4096, dim=dim),
               lexical_cfg=LexicalConfig(vocab_size=512,
                                         doc_terms=ccfg.doc_terms))
    corpus = make_corpus(ccfg)
    db.ingest(corpus)
    return db, ccfg, corpus


def _recall_at10(db, q, terms_list, relevant, *, match):
    doc_ids = np.asarray(db.log.snapshot()["doc_id"])
    admin = db.admin_session()
    total = 0.0
    for i in range(len(q)):
        b = admin.search(q[i])
        if match:
            b = b.match(terms_list[i])
        res = b.limit(10).run()
        got = {int(doc_ids[s]) for s in res.slots[0] if s >= 0}
        rel = set(relevant[i].tolist())
        total += len(got & rel) / min(10, len(rel))
    return total / len(q)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_hybrid_recall_beats_dense_on_keyword_grid(seed):
    db, ccfg, corpus = _keyword_db(seed)
    q, terms_list, relevant = make_keyword_queries(ccfg, corpus, 12,
                                                   seed=seed + 100)
    dense = _recall_at10(db, q, terms_list, relevant, match=False)
    hybrid = _recall_at10(db, q, terms_list, relevant, match=True)
    assert hybrid > dense, (seed, hybrid, dense)
    assert hybrid >= 0.9, "keyword-anchored hybrid recall collapsed"


# ---------------------------------------------------------------------------
# result-cache exactness across lexical writes
# ---------------------------------------------------------------------------

def _one_doc(ccfg, doc_id, terms):
    rng = np.random.default_rng(doc_id)
    emb = rng.standard_normal(ccfg.dim).astype(np.float32)
    return DocBatch(
        emb=jnp.asarray(emb[None, :]),
        tenant=jnp.asarray([0], jnp.int32),
        category=jnp.asarray([0], jnp.int32),
        updated_at=jnp.asarray([ccfg.now_ts], jnp.int32),
        acl=jnp.asarray([0xFFFFFFFF], jnp.uint32),
        doc_id=jnp.asarray([doc_id], jnp.int32),
        terms=jnp.asarray(np.asarray(terms, np.int32)[None, :]),
        tfs=jnp.asarray(np.full((1, len(terms)), 2, np.int32)))


def test_result_cache_exact_across_lexical_writes(rng):
    """A lexical write must make the pre-write cache entry unreachable
    (commit-counter keying) and the post-write result must equal a fresh
    uncached computation bit-for-bit — including the idf/avgdl drift the
    new postings cause. The query matches a term NO existing doc carries,
    so the post-write winner is fully determined: the ingested doc."""
    db, ccfg, corpus = _keyword_db(7, n_docs=800)
    q, _, _ = make_keyword_queries(ccfg, corpus, 1, seed=3)
    unused = np.nonzero(db.lex.stats.df == 0)[0]
    assert len(unused), "corpus saturated the vocab — enlarge vocab_size"
    u = int(unused[-1])
    admin = db.admin_session()
    run = lambda: admin.search(q[0]).match([u]).limit(5).run()
    r0 = run()
    assert not r0.cached and run().cached
    # a write carrying postings: bumps commit_count AND LexicalStats
    db.ingest(_one_doc(ccfg, 990_000, [u]))
    r1 = run()
    assert not r1.cached, "stale hybrid hit across a lexical write"
    fresh = db.execute([admin.search(q[0]).match([u]).limit(5).plan()],
                       use_cache=False)
    assert (r1.scores == fresh[0]).all() and (r1.slots == fresh[1]).all()
    # the sole carrier of the matched term must now be the top-1 result
    assert r1.slots[0][0] == db.log.slot_of(990_000)
    assert r0.slots[0][0] != r1.slots[0][0]


def test_result_cache_keys_on_lexical_stats_version():
    """Hot-only hybrid entries must also drop when ONLY the corpus-level
    lexical statistics move (e.g. a write on the other tier shifting
    idf/avgdl) — the stats version is part of the key."""
    db, ccfg, corpus = _keyword_db(8, n_docs=600)
    q, terms_list, _ = make_keyword_queries(ccfg, corpus, 1, seed=4)
    admin = db.admin_session()
    run = lambda: admin.search(q[0]).match(terms_list[0]).limit(5).run()
    run()
    assert run().cached
    # poke the shared stats WITHOUT an arena commit (simulates a sibling
    # tier's lexical write): the cached entry must become unreachable
    db.lex.stats.add(np.asarray([[int(terms_list[0][0])]]),
                     np.asarray([[3]]))
    assert not run().cached


# ---------------------------------------------------------------------------
# planner rules
# ---------------------------------------------------------------------------

def test_planner_dense_fallback_without_match(rng):
    db, ccfg, _ = _keyword_db(9, n_docs=400)
    admin = db.admin_session()
    q = rng.standard_normal(ccfg.dim).astype(np.float32)
    plan = admin.search(q).limit(5).plan()
    assert plan.engine != "hybrid"          # no clause, no hybrid
    assert plan.lex is None
    hyb = admin.search(q).match([5, 9]).limit(5).plan()
    assert hyb.engine == "hybrid"
    assert hyb.lex == ("wsum", 2, 1.0, 1.0)
    assert "score mix wsum" in hyb.explain()
    # the lexical clause shows up in the predicate line and the group key
    assert "match(2 terms)" in hyb.explain()
    assert hyb.group_key != plan.group_key


def test_planner_refuses_engine_conflicts(rng):
    db, ccfg, _ = _keyword_db(10, n_docs=400)
    admin = db.admin_session()
    q = rng.standard_normal(ccfg.dim).astype(np.float32)
    with pytest.raises(ValueError, match="hybrid engine"):
        admin.search(q).match([3]).using("ref").plan()
    with pytest.raises(ValueError, match="match\\(\\) clause"):
        admin.search(q).using("hybrid").plan()
    # fuse() without a clause must be loud too — never silently inert
    with pytest.raises(ValueError, match="fuse\\(\\) requires"):
        admin.search(q).fuse("rrf").plan()
    with pytest.raises(ValueError, match="fuse\\(\\) requires"):
        admin.search(q).fuse("wsum", w_lex=2.0).plan()
    with pytest.raises(ValueError, match="lexical arena"):
        choose_engine(LogicalPlan(match_terms=(3,), k=5), n_rows=64)
    db_plain = RagDB(StoreConfig(capacity=64, dim=8))
    with pytest.raises(ValueError, match="lexical arena"):
        db_plain.admin_session().search(np.zeros(8, np.float32)).match([1])


def test_planner_prices_hybrid_from_cost_model(rng):
    db, ccfg, _ = _keyword_db(11, n_docs=400)
    cm = CostModel(curves=(("hybrid", ((256, 0.5), (4096, 4.0))),))
    db.planner_cfg = PlannerConfig(cost_model=cm)
    q = rng.standard_normal(ccfg.dim).astype(np.float32)
    plan = db.admin_session().search(q).match([3, 4]).limit(5).plan()
    assert plan.engine == "hybrid" and plan.est_cost_ms is not None
    assert "cost model" in plan.engine_reason


# ---------------------------------------------------------------------------
# fusion: hybrid groups share one scan; fused == looped bit-identically
# ---------------------------------------------------------------------------

def test_hybrid_groups_fuse_into_one_scan(rng):
    db, ccfg, corpus = _keyword_db(12, n_docs=900)
    q, terms_list, _ = make_keyword_queries(ccfg, corpus, 6, seed=5)
    arena = db.log.snapshot()["emb"].shape[0]
    t_lanes = db.lex.cfg.doc_terms

    def plans():
        out = []
        for i in range(6):
            sess = db.session(Principal(tenant_id=i % 3,
                                        group_bits=0xFFFFFFFF))
            out.append(sess.search(q[i]).match(terms_list[i])
                       .limit(5).plan())
        return out

    ps = plans()
    assert all(p.fusable and p.engine == "hybrid" for p in ps)
    rows0, scans0, terms0 = (db.stats.rows_scanned, db.stats.fused_scans,
                             db.stats.terms_scanned)
    fs, fi, ft = db.execute(ps, use_cache=False)
    assert db.stats.rows_scanned - rows0 == arena     # ONE pass for 3 groups
    assert db.stats.terms_scanned - terms0 == arena * t_lanes
    assert db.stats.fused_scans == scans0 + 1
    db.planner_cfg = dataclasses.replace(db.planner_cfg,
                                         fuse_min_groups=1 << 30)
    ls, li, lt = db.execute(plans(), use_cache=False)
    db.planner_cfg = PlannerConfig()
    assert (fs == ls).all() and (fi == li).all() and (ft == lt).all()


def test_hybrid_never_fuses_with_dense_groups(rng):
    db, ccfg, corpus = _keyword_db(13, n_docs=600)
    q, terms_list, _ = make_keyword_queries(ccfg, corpus, 2, seed=6)
    admin = db.admin_session()
    hyb = admin.search(q[0]).match(terms_list[0]).limit(5).plan()
    dense = admin.search(q[1]).limit(5).plan()
    assert hyb.fuse_key != dense.fuse_key
    calls0 = db.stats.device_calls
    db.execute([hyb, dense], use_cache=False)
    assert db.stats.device_calls - calls0 == 2        # one scan each


@pytest.mark.parametrize("mix,launches,joins", [
    # 1 + 3 + 1 rows of buckets 1, 4, 16: one launch at qt 16
    ([("wsum", 1, 1), ("wsum", 4, 3), ("wsum", 16, 1)],
     [("wsum", 16, 5, 3)], 1),
    # wsum and rrf never join, whatever their buckets
    ([("wsum", 1, 1), ("rrf", 16, 1)],
     [("rrf", 16, 1, 1), ("wsum", 1, 1, 1)], 0),
    # rrf joins like wsum
    ([("rrf", 2, 1), ("rrf", 8, 2)], [("rrf", 8, 3, 2)], 1),
    # 8 + 8 rows: a joined launch makes two passes, as the two apart do
    ([("wsum", 1, 8), ("wsum", 16, 8)],
     [("wsum", 1, 8, 1), ("wsum", 16, 8, 1)], 0),
    # 9 + 1 rows: two passes replace three
    ([("wsum", 1, 9), ("wsum", 16, 1)], [("wsum", 16, 10, 2)], 1),
    # 2 + 2 rows of one bucket: one launch, as before the join
    ([("wsum", 4, 2), ("wsum", 4, 2)], [("wsum", 4, 4, 1)], 0),
])
def test_hybrid_bucket_join_rule(mix, launches, joins):
    """Hybrid groups of one score mix join across query-term buckets while
    the joined launch makes fewer 8-row passes than the launches it
    replaces; each launch runs at its largest member bucket (``qt``) and
    says how many buckets it joined (``qt_joined``). Entry i of ``mix`` is
    (mode, bucket, rows), its rows one predicate group of tenant i."""
    from repro.obs import FlightRecorder, Tracer
    db, ccfg, corpus = _keyword_db(17, n_docs=600)
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    rng = np.random.default_rng(3)
    plans = []
    for t, (mode, bucket, rows) in enumerate(mix):
        sess = db.session(Principal(tenant_id=t, group_bits=0xFFFFFFFF))
        for _ in range(rows):
            b = sess.search(rng.standard_normal(ccfg.dim).astype(np.float32))
            plans.append(b.match(list(range(bucket))).fuse(mode).limit(5)
                         .plan())
    joins0 = db.stats.lex_bucket_joins
    db.execute(plans, use_cache=False)
    units = {s.ann["unit"]: s.ann for t in rec.traces() for s in t.spans
             if s.name == "launch"}
    got = sorted((a["mode"], a["qt"], a["rows"], a["qt_joined"])
                 for a in units.values())
    assert got == launches
    assert db.stats.lex_bucket_joins - joins0 == joins


# ---------------------------------------------------------------------------
# warm-tier lexical pushdown
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["wsum", "rrf"])
def test_warm_tier_lexical_pushdown(mode):
    """A tiered RagDB answers hybrid queries across BOTH tiers: the warm
    probe pushes predicate AND query terms into one round trip, and warm
    rows surface in the merge when their fused score earns it."""
    ccfg = CorpusConfig(n_docs=1500, dim=16, seed=21, vocab_size=256,
                        n_topics=8, n_entity_terms=32, entity_frac=0.06)
    scfg = StoreConfig(capacity=2048, dim=16)
    db = RagDB(scfg, warm_cfg=scfg, hot_window_s=90 * DAY_S,
               now_ts=ccfg.now_ts,
               lexical_cfg=LexicalConfig(vocab_size=256,
                                         doc_terms=ccfg.doc_terms))
    corpus = make_corpus(ccfg)
    db.ingest(corpus)
    assert db.router.warm.lex is not None and db.router.warm.n_docs > 0
    q, terms_list, relevant = make_keyword_queries(ccfg, corpus, 6, seed=7)
    admin = db.admin_session()
    hot_ids = np.asarray(db.log.snapshot()["doc_id"])
    warm_ids = np.asarray(db.router.warm.meta["doc_id"])
    saw_warm = False
    total = 0.0
    for i in range(len(q)):
        rt0 = db.router.warm.stats.round_trips
        res = (admin.search(q[i]).match(terms_list[i]).fuse(mode)
               .limit(10).run())
        assert res.plan.route == "hot+warm"
        assert db.router.warm.stats.round_trips - rt0 == 1   # ONE pushdown
        got = set()
        for s, t in zip(res.slots[0], res.tiers[0]):
            if s >= 0:
                got.add(int(hot_ids[s] if t == 0 else warm_ids[s]))
                saw_warm |= bool(t == 1)
        rel = set(relevant[i].tolist())
        total += len(got & rel) / min(10, len(rel))
    assert saw_warm, "warm tier never contributed — pushdown untested"
    assert total / len(q) >= 0.9


def test_serving_engine_hybrid_request(rng):
    """A keyword-anchored serving request rides the same batch as dense
    requests: the match clause lowers through the session API, the plan
    runs on the hybrid engine, and provenance stays tenant-scoped."""
    import jax
    from repro.models.transformer import TransformerConfig, init
    from repro.serving.engine import RAGEngine, Request
    db, ccfg, corpus = _keyword_db(15, n_docs=900)
    q, terms_list, _ = make_keyword_queries(ccfg, corpus, 2, seed=11)
    cfg = TransformerConfig(name="gen", n_layers=1, d_model=32, n_heads=4,
                            n_kv_heads=2, d_ff=64, vocab_size=128,
                            dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    engine = RAGEngine(db, cfg, params, k=3, max_prompt=16, max_len=24)
    tenant_of = np.asarray(db.log.snapshot()["tenant"])
    reqs = [Request(principal=Principal(tenant_id=1, group_bits=0xFFFFFFFF),
                    query_emb=q[0], match_terms=terms_list[0],
                    prompt_tokens=np.asarray([5, 6], np.int32),
                    max_new_tokens=2),
            Request(principal=Principal(tenant_id=2, group_bits=0xFFFFFFFF),
                    query_emb=q[1],
                    prompt_tokens=np.asarray([7], np.int32),
                    max_new_tokens=2)]
    resps = engine.serve(reqs)
    got = resps[0].doc_slots[resps[0].doc_slots >= 0]
    assert len(got) and (tenant_of[got] == 1).all()
    got2 = resps[1].doc_slots[resps[1].doc_slots >= 0]
    assert len(got2) and (tenant_of[got2] == 2).all()
    # raw-store path cannot express the clause
    raw = RAGEngine(db.log.snapshot(), cfg, params, k=3, max_prompt=16,
                    max_len=24)
    with pytest.raises(ValueError, match="front-door"):
        raw.serve(reqs)


def test_two_scan_baseline_agrees_on_clear_winners():
    """The split baseline is approximate (union-of-top-C) but must agree
    with the fused scan on keyword-anchored queries whose winners are
    unambiguous — it is the bench's comparison target, not a strawman."""
    db, ccfg, corpus = _keyword_db(14, n_docs=800)
    q, terms_list, _ = make_keyword_queries(ccfg, corpus, 4, seed=8)
    admin = db.admin_session()
    snap = db.log.snapshot()
    lex_snap = db.lex.snapshot()
    for i in range(len(q)):
        res = admin.search(q[i]).match(terms_list[i]).limit(5).run()
        qt = np.asarray(terms_list[i], np.int32)[None, :]
        s2, i2 = two_scan_hybrid(snap, lex_snap, q[i][None, :], qt,
                                 Predicate(), 5)
        assert set(i2[0].tolist()) == set(res.slots[0].tolist())
