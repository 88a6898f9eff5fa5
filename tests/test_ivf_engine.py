"""The ivf engine end to end (sub-linear retrieval PR).

Acceptance contracts:
  * isolation through the pruned route is STRUCTURAL — the predicate mask
    reads arena metadata, so even an adversarially poisoned member table
    cannot surface a row that fails the predicate;
  * recall@10 >= 0.95 vs the exact ref scan across a seed grid;
  * the Pallas probe kernel (interpret mode) is bit-identical to the jnp
    ref probe;
  * the planner's selectivity guard falls back to an exact engine with an
    auditable reason; `.using("ivf")` overrides it;
  * the result cache stays snapshot-exact across writes that touch the
    index and across index rebuilds (epoch-keyed);
  * build overflow rows are scanned exactly, never dropped from recall;
  * `ExecStats.rows_scanned` audits the pruning: probed scans stay under
    25% of the arena, exact scans count the full arena.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import LogicalPlan, RagDB
from repro.api.planner import choose_engine
from repro.core import Predicate, Principal, StoreConfig
from repro.core.ivf import IVFConfig, build_ivf, ivf_probe
from repro.data.corpus import CorpusConfig, make_corpus, make_queries

pytestmark = [pytest.mark.kernels, pytest.mark.slow]


def _db(n_docs=4000, dim=32, n_tenants=4, seed=0, index_cfg=None, **kwargs):
    ccfg = CorpusConfig(n_docs=n_docs, dim=dim, n_tenants=n_tenants,
                        n_categories=4, seed=seed)
    cap = 1 << int(np.ceil(np.log2(n_docs)) + 1)
    db = RagDB(StoreConfig(capacity=cap, dim=dim), **kwargs)
    db.ingest(make_corpus(ccfg))
    db.build_index(index_cfg)
    return db, ccfg


@pytest.fixture(scope="module")
def db_stack():
    return _db()


# ---------------------------------------------------------------------------
# recall vs the exact scan (seed grid)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_recall_at_10_on_seed_grid(seed):
    db, ccfg = _db(n_docs=3000, dim=32, seed=seed)
    admin = db.admin_session()
    qs = np.asarray(make_queries(ccfg, 16, batch=1, seed=seed + 100))
    hits = total = 0
    for q in qs:
        iv = admin.search(q[0]).limit(10).using("ivf").run()
        ex = admin.search(q[0]).limit(10).using("ref").run()
        hits += len(set(iv.slots[0].tolist()) & set(ex.slots[0].tolist()))
        total += 10
    assert hits / total >= 0.95, f"recall@10 {hits / total:.3f} below bar"


# ---------------------------------------------------------------------------
# kernel vs ref probe: bit identity in interpret mode (tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,dim,k,cap_override,B", [
    (1500, 32, 5, None, 2),
    (1200, 48, 8, 64, 4),     # D not a lane multiple + forced overflow tail
    (900, 64, 10, None, 11),  # B above blk_b -> query-row padding path
])
def test_probe_kernel_bit_identical_to_ref(n, dim, k, cap_override, B, rng):
    ccfg = CorpusConfig(n_docs=n, dim=dim, n_tenants=4, n_categories=4)
    from repro.core import TransactionLog, empty
    scfg = StoreConfig(capacity=1 << int(np.ceil(np.log2(n)) + 1), dim=dim)
    log = TransactionLog(scfg, empty(scfg))
    log.ingest(make_corpus(ccfg))
    snap = log.snapshot()
    index = build_ivf(snap, IVFConfig(n_clusters=16, cluster_cap=cap_override))
    if cap_override is not None:
        assert len(index.overflow) > 0, "this case must exercise the tail"
    q = np.asarray(make_queries(ccfg, 1, batch=B, seed=7))[0]
    clusters, _, _ = index.probe(q, nprobe=6)
    dev = index.device_arrays()
    pred = Predicate(min_ts=3, cat_mask=0b0111).as_array()
    args = (jnp.asarray(q), snap, dev["members"], dev["overflow"], clusters,
            pred, k)
    s_ref, i_ref = ivf_probe(*args, use_kernel=False)
    s_ker, i_ker = ivf_probe(*args, use_kernel=True, interpret=True)
    assert (np.asarray(s_ref) == np.asarray(s_ker)).all()
    assert (np.asarray(i_ref) == np.asarray(i_ker)).all()


# ---------------------------------------------------------------------------
# isolation: a poisoned member table cannot leak
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_poisoned_member_table_cannot_leak(seed):
    """Adversarial index corruption — wrong-cluster slots, duplicate slots,
    tombstoned slots, out-of-range slots — may cost recall, never isolation:
    the mask reads ARENA metadata inside the probe scan."""
    db, ccfg = _db(n_docs=800, dim=16, seed=seed)
    prng = np.random.default_rng(seed)
    ix = db.index
    # poison ~25% of member entries + the overflow tail
    poison = prng.random(ix.members.shape) < 0.25
    junk = prng.integers(-5, db.hot_cfg.capacity + 500, ix.members.shape)
    ix.members[poison] = junk[poison]
    ix.overflow = [int(x) for x in
                   prng.integers(-5, db.hot_cfg.capacity + 500, 16)]
    ix._dev = None
    snap = db.log.snapshot()
    tenant_of = np.asarray(snap["tenant"])
    ts_of = np.asarray(snap["updated_at"])
    q = np.asarray(make_queries(ccfg, 1, batch=2, seed=seed))[0]
    min_ts = ccfg.now_ts // 3
    for t in range(ccfg.n_tenants):
        sess = db.session(Principal(tenant_id=t, group_bits=0xFFFFFFFF))
        res = (sess.search(q).newer_than(min_ts).limit(8)
               .using("ivf").run())
        got = res.slots[res.slots >= 0]
        assert (got < db.hot_cfg.capacity).all() and (got >= 0).all()
        assert (tenant_of[got] == t).all(), "poisoned member table leaked"
        assert (ts_of[got] >= min_ts).all()


# ---------------------------------------------------------------------------
# planner: selectivity guard + hint override + explain
# ---------------------------------------------------------------------------

def test_planner_prefers_ivf_when_index_present():
    eng, why = choose_engine(LogicalPlan(k=5), n_rows=1 << 16, has_index=True)
    assert eng == "ivf" and "index present" in why
    # small arena: exact scan is trivially fast, no point probing
    eng, _ = choose_engine(LogicalPlan(k=5), n_rows=1 << 10, has_index=True)
    assert eng == "ref"
    # no index: nothing changes
    eng, _ = choose_engine(LogicalPlan(k=5), n_rows=1 << 16)
    assert eng == "ref"


def test_planner_falls_back_on_selective_predicates(db_stack):
    db, ccfg = db_stack
    q = np.asarray(make_queries(ccfg, 1))[0][0]
    admin_plan = db.admin_session().search(q).limit(5).plan()
    assert admin_plan.engine == "ivf"
    sess = db.session(Principal(tenant_id=1, group_bits=0xFFFFFFFF))
    plan = sess.search(q).limit(5).plan()
    assert plan.engine != "ivf"
    assert "ivf skipped" in plan.engine_reason
    assert "under-fill" in plan.engine_reason
    # recency alone is NOT selective for the guard (hot tier covers it)
    recency = db.admin_session().search(q).newer_than(5).limit(5).plan()
    assert recency.engine == "ivf"
    # the caller hint overrides the guard; isolation still holds
    forced = sess.search(q).limit(8).using("ivf").run()
    tenant_of = np.asarray(db.log.snapshot()["tenant"])
    got = forced.slots[forced.slots >= 0]
    assert (tenant_of[got] == 1).all()


def test_ivf_plan_explain_reports_probe_budget(db_stack):
    db, ccfg = db_stack
    q = np.asarray(make_queries(ccfg, 1))[0][0]
    plan = db.admin_session().search(q).limit(5).plan()
    text = plan.explain()
    assert f"nprobe={plan.nprobe}" in text
    assert "candidate rows" in text and "% of arena" in text
    n_clusters, cap, est = plan.ivf_est
    assert est < 0.25 * plan.n_rows, "probe budget must stay sub-linear"
    assert str(plan.nprobe) in text and plan.nprobe in plan.group_key


def test_using_ivf_without_index_raises():
    db = RagDB(StoreConfig(capacity=256, dim=8))
    from tests.test_core_store import make_batch
    db.ingest(make_batch(np.random.default_rng(0), 8, 8, tenant=0))
    with pytest.raises(ValueError, match="build_index"):
        db.admin_session().search(np.ones(8, np.float32)).using("ivf").plan()


# ---------------------------------------------------------------------------
# rows_scanned audit (the count that catches exact-scan regressions)
# ---------------------------------------------------------------------------

def test_rows_scanned_audits_pruning(db_stack):
    db, ccfg = db_stack
    admin = db.admin_session()
    q = np.asarray(make_queries(ccfg, 1, seed=42))[0][0]
    arena = db.hot_cfg.capacity
    before = db.stats.rows_scanned
    admin.search(q).limit(5).using("ref").run()
    assert db.stats.rows_scanned == before + arena
    before = db.stats.rows_scanned
    res = admin.search(q + 0.01).limit(5).run()       # planner's choice: ivf
    assert res.plan.engine == "ivf"
    scanned = db.stats.rows_scanned - before
    assert 0 < scanned < 0.25 * arena, scanned


def test_tight_recency_bound_never_underfills(db_stack):
    """Recency-only plans stay on ivf, but a bound so tight that qualifying
    rows sit outside the probed clusters must not shrink the k-list: the
    executor's exact-rescan net completes it, bit-identical to ref."""
    db, ccfg = db_stack
    admin = db.admin_session()
    ts = np.asarray(db.log.snapshot()["updated_at"])
    # a bound only ~20 live rows clear — far fewer than any probe covers
    min_ts = int(np.sort(ts)[-20])
    q = np.asarray(make_queries(ccfg, 1, seed=21))[0][0]
    plan = admin.search(q).newer_than(min_ts).limit(10).plan()
    assert plan.engine == "ivf"
    res = admin.search(q).newer_than(min_ts).limit(10).run()
    ref = admin.search(q).newer_than(min_ts).limit(10).using("ref").run()
    assert (res.slots == ref.slots).all()
    assert (res.scores == ref.scores).all()


# ---------------------------------------------------------------------------
# overflow tail: scanned exactly, never dropped
# ---------------------------------------------------------------------------

def test_overflow_rows_stay_in_recall(rng):
    """With a cap far below the biggest cluster, the spill lands in the
    overflow tail. Probing ALL clusters must then equal the exact scan —
    which is only possible if the tail is scanned, not dropped."""
    ccfg = CorpusConfig(n_docs=1000, dim=16, n_tenants=3, n_categories=4)
    from repro.core import TransactionLog, empty, unified_query
    scfg = StoreConfig(capacity=2048, dim=16)
    log = TransactionLog(scfg, empty(scfg))
    log.ingest(make_corpus(ccfg))
    snap = log.snapshot()
    index = build_ivf(snap, IVFConfig(n_clusters=8, cluster_cap=64))
    assert len(index.overflow) > 0
    assert int(index.fill.sum()) + len(index.overflow) == 1000
    from repro.core.ivf import ivf_query
    q = np.asarray(make_queries(ccfg, 1, batch=3, seed=5))[0]
    pred = Predicate(min_ts=ccfg.now_ts // 4)
    s_iv, i_iv = ivf_query(snap, index, jnp.asarray(q), pred, 10,
                           nprobe=index.n_clusters)
    s_ex, i_ex = unified_query(snap, jnp.asarray(q), pred, 10)
    for b in range(3):
        assert set(np.asarray(i_iv)[b].tolist()) == \
            set(np.asarray(i_ex)[b].tolist())


# ---------------------------------------------------------------------------
# maintenance: write-through, drift rebuild, cache exactness
# ---------------------------------------------------------------------------

def test_ingest_and_delete_write_through_to_index(rng):
    db, ccfg = _db(n_docs=1200, dim=16)
    from tests.test_core_store import make_batch
    admin = db.admin_session()
    new = make_batch(rng, 1, 16, tenant=0, start_id=50_000)
    db.ingest(new)
    slot = db.log.slot_of(50_000)
    q = np.asarray(new.emb)[0]
    res = admin.search(q).limit(3).using("ivf").run()
    assert slot == res.slots[0, 0], "fresh row must be probeable immediately"
    db.delete([50_000])
    res2 = admin.search(q).limit(3).using("ivf").run()
    assert slot not in res2.slots[0].tolist()
    # index bookkeeping stays consistent through the churn
    ix = db.index
    assert int(ix.fill.sum()) + len(ix.overflow) == int(
        db.log.snapshot()["n_live"])


def test_drift_threshold_triggers_rebuild(rng):
    db, ccfg = _db(n_docs=600, dim=16,
                   index_cfg=IVFConfig(n_clusters=16,
                                       drift_rebuild_frac=0.05))
    from tests.test_core_store import make_batch
    assert db.index.epoch == 0
    db.ingest(make_batch(rng, 40, 16, tenant=0, start_id=90_000))  # > 5% churn
    assert db.index.epoch == 1, "drift past the threshold must rebuild"
    assert db.index.churn == 0


def test_cache_exact_across_ingest_touching_index(rng):
    db, ccfg = _db(n_docs=1500, dim=16)
    admin = db.admin_session()
    q = np.asarray(make_queries(ccfg, 1, seed=9))[0][0]
    base = admin.search(q).limit(5).run()
    assert base.plan.engine == "ivf"
    assert admin.search(q).limit(5).run().cached
    # ingest a doc embedded AT the query: the probe's answer must change
    from repro.core.store import DocBatch
    db.ingest(DocBatch(
        emb=jnp.asarray(q[None, :]), tenant=jnp.asarray([0]),
        category=jnp.asarray([0]), updated_at=jnp.asarray([ccfg.now_ts]),
        acl=jnp.asarray([0xFFFFFFFF], jnp.uint32),
        doc_id=jnp.asarray([70_000])))
    fresh = admin.search(q).limit(5).run()
    assert not fresh.cached, "post-write hit would be stale"
    assert db.log.slot_of(70_000) == fresh.slots[0, 0]
    # determinism: the same snapshot serves the identical answer again
    again = admin.search(q).limit(5).run()
    assert again.cached and (again.slots == fresh.slots).all()


def test_rebuild_epoch_invalidates_ivf_entries(rng):
    db, ccfg = _db(n_docs=1500, dim=16)
    admin = db.admin_session()
    q = np.asarray(make_queries(ccfg, 1, seed=11))[0][0]
    base = admin.search(q).limit(5).run()
    assert admin.search(q).limit(5).run().cached
    db.build_index(db.index.cfg)          # rebuild: no arena commit, new epoch
    post = admin.search(q).limit(5).run()
    assert not post.cached, "rebuild changes scoring; epoch key must miss"
    # exact-engine entries are epoch-independent and still hit
    ref = admin.search(q).limit(5).using("ref").run()
    assert admin.search(q).limit(5).using("ref").run().cached


def test_device_mirror_patches_in_place(rng):
    """IVF device-mirror granularity (ROADMAP item): a write patches only
    the touched member-table rows on the next probe — upload bytes scale
    with the write, not the (C, cap) table — and the patched mirror stays
    equal to the host truth."""
    from tests.test_core_store import make_batch
    db, ccfg = _db(n_docs=3000, dim=16)
    ix = db.index
    admin = db.admin_session()
    q = np.asarray(make_queries(ccfg, 1, seed=21))[0][0]
    admin.search(q).limit(5).using("ivf").run()          # full upload
    assert ix.mirror_uploads == 1 and ix.mirror_patches == 0
    full_bytes = ix.mirror_bytes_uploaded
    assert full_bytes >= ix.members.nbytes

    db.ingest(make_batch(rng, 2, ccfg.dim, tenant=0, start_id=80_000))
    admin.search(q + 0.01).limit(5).using("ivf").run()   # patched upload
    patch_bytes = ix.mirror_bytes_uploaded - full_bytes
    assert ix.mirror_uploads == 1, "a write must NOT re-upload the mirror"
    assert ix.mirror_patches >= 1
    assert 0 < patch_bytes <= 2 * ix.cluster_cap * 4 + 1024, (
        f"patch uploaded {patch_bytes}B; expected <= the touched rows")
    assert patch_bytes * 4 < ix.members.nbytes, "upload bytes must shrink"
    # the patched mirror is the host truth, bit for bit
    dev = ix.device_arrays()
    assert np.array_equal(np.asarray(dev["members"]), ix.members)
    over = np.asarray(dev["overflow"])
    assert set(over[over >= 0].tolist()) == set(ix.overflow)

    # a delete that touches a member row patches too (swap-with-last)
    victim = int(np.asarray(db.log.snapshot()["doc_id"])[ix.members[
        ix.members >= 0][0]])
    before = ix.mirror_bytes_uploaded
    db.delete([victim])
    admin.search(q + 0.02).limit(5).using("ivf").run()
    assert ix.mirror_uploads == 1
    assert ix.mirror_bytes_uploaded - before < ix.members.nbytes
    dev = ix.device_arrays()
    assert np.array_equal(np.asarray(dev["members"]), ix.members)
