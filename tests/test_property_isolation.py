"""Property tests on the system's central invariants.

1. LEAKAGE-IMPOSSIBILITY: for ANY corpus, ANY predicate, ANY query, no row
   returned by the unified engine violates the predicate (the paper's
   row-level-security claim, attacked adversarially).
2. TOP-K SOUNDNESS: returned scores are the true top-k of the masked score
   vector, in non-increasing order.
3. The arena-scan Pallas kernel (filtered preset: one predicate group)
   satisfies the same contract as the ref.
4. FRONT DOOR: the same two properties hold through `RagDB`/`Session` — a
   builder chain is bit-identical to the direct reference call, and no
   Session can surface another tenant's rows (the API cannot even express
   the request).

Runs under Hypothesis when installed; otherwise the same checks sweep a
deterministic seed grid so the invariants stay enforced on minimal CI rigs.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RagDB
from repro.core import Principal, StoreConfig
from repro.core.query import Predicate, unified_query_ref
from repro.core.store import DocBatch
from repro.kernels.arena_scan.ops import arena_scan

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False


def _store_from(emb, tenant, ts, cat, acl):
    n = emb.shape[0]
    return {
        "emb": jnp.asarray(emb), "tenant": jnp.asarray(tenant),
        "category": jnp.asarray(cat), "updated_at": jnp.asarray(ts),
        "acl": jnp.asarray(acl, jnp.uint32),
        "doc_id": jnp.arange(n, dtype=jnp.int32),
        "version": jnp.zeros(n, jnp.int32),
        "commit_ts": jnp.int32(1), "n_live": jnp.int32(n),
    }


def _args_from_seed(seed: int):
    """Deterministic draw matching the hypothesis strategy's support
    (endpoint=True so the ALL_BITS pass-all sentinels are reachable)."""
    rng = np.random.default_rng(seed)
    return (int(rng.integers(4, 301)), int(rng.integers(0, 2**32)),
            int(rng.integers(-2, 6)), int(rng.integers(0, 501)),
            int(rng.integers(1, 0xFFFFFFFF, endpoint=True)),
            int(rng.integers(1, 0xFFFFFFFF, endpoint=True)),
            int(rng.integers(1, 13)))


def _corpus(args):
    n, seed, p_ten, p_ts, p_cat, p_acl, k = args
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n, 8), dtype=np.float32)
    tenant = rng.integers(-1, 6, n, dtype=np.int32)     # -1 = tombstones
    ts = rng.integers(0, 600, n, dtype=np.int32)
    cat = rng.integers(0, 32, n, dtype=np.int32)
    acl = rng.integers(0, 2**31, n, dtype=np.int64).astype(np.uint32)
    pred = Predicate(tenant=p_ten, min_ts=p_ts, cat_mask=p_cat, acl_bits=p_acl)
    q = rng.standard_normal((2, 8), dtype=np.float32)
    return emb, tenant, ts, cat, acl, pred, q, k


def _oracle_mask(tenant, ts, cat, acl, pred):
    mask = (tenant >= 0) & (ts >= pred.min_ts)
    if pred.tenant != -2:
        mask &= tenant == pred.tenant
    mask &= ((np.uint64(1) << (cat.astype(np.uint64) & np.uint64(31)))
             & np.uint64(pred.cat_mask)) != 0
    mask &= (acl & np.uint32(pred.acl_bits)) != 0
    return mask


def _check_no_leak_and_topk(args):
    emb, tenant, ts, cat, acl, pred, q, k = _corpus(args)
    store = _store_from(emb, tenant, ts, cat, acl)
    scores, slots = unified_query_ref(store, jnp.asarray(q), pred.as_array(), k)
    scores, slots = np.asarray(scores), np.asarray(slots)

    mask = _oracle_mask(tenant, ts, cat, acl, pred)
    ref = q @ emb.T
    ref[:, ~mask] = -np.inf

    for b in range(2):
        # 1. no returned slot violates the predicate
        got = slots[b][slots[b] >= 0]
        assert mask[got].all(), "LEAK: predicate-violating row returned"
        # 2. exactly min(k, qualifying) rows returned
        assert len(got) == min(k, int(mask.sum()))
        # 3. scores are the true top-k, non-increasing
        want = np.sort(ref[b][mask])[::-1][: len(got)]
        have = scores[b][scores[b] > -1e38]
        assert (np.diff(have) <= 1e-6).all()
        np.testing.assert_allclose(have, want, rtol=1e-4, atol=1e-5)


def _check_pallas_same_contract(args):
    emb, tenant, ts, cat, acl, pred, q, k = _corpus(args)
    store = _store_from(emb, tenant, ts, cat, acl)
    s_ref, _ = unified_query_ref(store, jnp.asarray(q), pred.as_array(), k)
    s_pal, i_pal = arena_scan(jnp.asarray(q), jnp.asarray(emb),
                              jnp.asarray(tenant), jnp.asarray(ts),
                              jnp.asarray(cat), jnp.asarray(acl, jnp.uint32),
                              np.zeros(len(q), np.int32),
                              pred.as_array()[None, :], k, use_kernel=True,
                              blk_n=64)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-5)


def _check_session_front_door(args):
    """The front door adds nothing and removes nothing: a Session's builder
    chain is bit-identical to the reference engine under the principal's
    clauses, and its results can never leave the principal's tenant/ACL."""
    emb, tenant, ts, cat, acl, pred, q, k = _corpus(args)
    n = emb.shape[0]
    db = RagDB(StoreConfig(capacity=n, dim=8, metric="dot"))
    db.ingest(DocBatch(emb=jnp.asarray(emb), tenant=jnp.asarray(tenant),
                       category=jnp.asarray(cat), updated_at=jnp.asarray(ts),
                       acl=jnp.asarray(acl, jnp.uint32),
                       doc_id=jnp.arange(n, dtype=jnp.int32)))
    principal_tenant = abs(pred.tenant) % 6
    principal = Principal(tenant_id=principal_tenant, group_bits=pred.acl_bits)
    res = (db.session(principal).search(q, normalize=False)
           .newer_than(pred.min_ts).limit(k).run())

    lowered = Predicate(tenant=principal_tenant, min_ts=pred.min_ts,
                        acl_bits=pred.acl_bits)
    s_ref, i_ref = unified_query_ref(db.log.snapshot(), jnp.asarray(q),
                                     lowered.as_array(), k)
    assert (np.asarray(i_ref) == res.slots).all()
    assert (np.asarray(s_ref) == res.scores).all()
    for b in range(2):
        got = res.slots[b][res.slots[b] >= 0]
        assert (tenant[got] == principal_tenant).all(), "cross-tenant leak"
        assert ((acl[got] & np.uint32(pred.acl_bits)) != 0).all(), "ACL leak"
        assert (ts[got] >= pred.min_ts).all()


def _check_scheduler_isolation(args):
    """Isolation survives the serving path: plans pushed through the
    admission-controlled scheduler — including ones it degrades under
    pressure or serves stale from cache — can never surface another
    tenant's rows or rows outside the principal's ACL. The scheduler
    never sees a principal; the clauses ride in the lowered plan."""
    from repro.serving.scheduler import (Scheduler, SchedulerConfig,
                                         ServeRequest)

    emb, tenant, ts, cat, acl, pred, q, k = _corpus(args)
    n = emb.shape[0]
    db = RagDB(StoreConfig(capacity=n, dim=8, metric="dot"))
    db.ingest(DocBatch(emb=jnp.asarray(emb), tenant=jnp.asarray(tenant),
                       category=jnp.asarray(cat), updated_at=jnp.asarray(ts),
                       acl=jnp.asarray(acl, jnp.uint32),
                       doc_id=jnp.arange(n, dtype=jnp.int32)))
    # tiny queue + aggressive thresholds: force the degradation/stale
    # machinery on, then serve the same plans twice so the second round
    # can hit the (stale-eligible) result cache
    sched = Scheduler(db, SchedulerConfig(
        slo_ms=0.0, max_queue=4, max_batch=2, degrade_pressure=0.0,
        stale_pressure=0.0, stale_within_s=60.0))
    principals = [Principal(tenant_id=t % 6, group_bits=pred.acl_bits)
                  for t in range(3)]
    plans = [db.session(p).search(q, normalize=False)
             .newer_than(pred.min_ts).limit(k).plan() for p in principals]
    for round_ in range(2):
        results = []
        for i, plan in enumerate(plans):
            if sched.offer(ServeRequest(plan=plan, arrival_t=sched.clock(),
                                        req_id=i)):
                results.extend(sched.run_until_idle())
        for res in results:
            p = principals[res.request.req_id]
            for b in range(q.shape[0]):
                got = res.slots[b][res.slots[b] >= 0]
                assert (tenant[got] == p.tenant_id).all(), \
                    f"cross-tenant leak via scheduler (served={res.served})"
                assert ((acl[got] & np.uint32(pred.acl_bits)) != 0).all(), \
                    f"ACL leak via scheduler (served={res.served})"
                assert (ts[got] >= pred.min_ts).all()


def _check_chaos_isolation(args):
    """Isolation survives the serving path UNDER FAULTS: with a storm firing
    on every query-path site (warm errors, hot-launch failures, finish
    faults, poisoned cache epochs), any row a non-failed response surfaces
    still satisfies the plan's tenant/ACL/ts clauses. Faults may degrade or
    fail a response — they can never widen it. Stall rates are zero so the
    property runs at full speed; the timing-dependent fault classes get
    their own fake-clock tests in tests/test_faults.py."""
    from repro.serving.faults import FaultPlan, FaultRule
    from repro.serving.scheduler import (Scheduler, SchedulerConfig,
                                         ServeRequest)

    emb, tenant, ts, cat, acl, pred, q, k = _corpus(args)
    n = emb.shape[0]
    db = RagDB(StoreConfig(capacity=n, dim=8, metric="dot"))
    db.ingest(DocBatch(emb=jnp.asarray(emb), tenant=jnp.asarray(tenant),
                       category=jnp.asarray(cat), updated_at=jnp.asarray(ts),
                       acl=jnp.asarray(acl, jnp.uint32),
                       doc_id=jnp.arange(n, dtype=jnp.int32)))
    storm_seed = args[1] & 0xFFFF
    db.attach_faults(FaultPlan(storm_seed, {
        "warm.error": FaultRule(rate=0.4),
        "hot.launch": FaultRule(rate=0.3),
        "hot.finish_error": FaultRule(rate=0.2),
        "cache.stale": FaultRule(rate=0.5),
    }))
    sched = Scheduler(db, SchedulerConfig(
        slo_ms=0.0, max_queue=8, max_batch=2, degrade_pressure=0.0,
        stale_pressure=0.0, stale_within_s=60.0, warm_retries=1,
        launch_retries=1, breaker_failures=3, breaker_reset_s=0.0,
        requeue_limit=1, seed=storm_seed))
    principals = [Principal(tenant_id=t % 6, group_bits=pred.acl_bits)
                  for t in range(3)]
    plans = [db.session(p).search(q, normalize=False)
             .newer_than(pred.min_ts).limit(k).plan() for p in principals]
    served = 0
    for round_ in range(2):
        results = []
        for i, plan in enumerate(plans):
            if sched.offer(ServeRequest(plan=plan, arrival_t=sched.clock(),
                                        req_id=i)):
                results.extend(sched.run_until_idle())
        for res in results:
            if res.served == "failed":
                assert (res.slots == -1).all()
                continue
            served += 1
            p = principals[res.request.req_id]
            for b in range(q.shape[0]):
                got = res.slots[b][res.slots[b] >= 0]
                assert (tenant[got] == p.tenant_id).all(), \
                    f"cross-tenant leak under faults (served={res.served})"
                assert ((acl[got] & np.uint32(pred.acl_bits)) != 0).all(), \
                    f"ACL leak under faults (served={res.served})"
                assert (ts[got] >= pred.min_ts).all()
    db.attach_faults(None)


def _check_sharded_affine_isolation(args):
    """SHARDED tenant-affine isolation: through a mesh-built RagDB (tenant
    placement over every local device — S=1 in the tier-1 process, S=8 when
    re-run from the distributed subprocess suite), a tenant-scoped query
    (a) scans ONLY its owning shard (per-shard rows audit), (b) never
    surfaces a POISONED foreign-tenant row crafted to out-score the whole
    corpus, and (c) returns exactly the reference engine's bits."""
    import jax

    from repro.launch.mesh import make_mesh

    emb, tenant, ts, cat, acl, pred, q, k = _corpus(args)
    n = emb.shape[0]
    S = jax.device_count()
    tenant = np.abs(tenant).astype(np.int32) % 6    # live rows (placement key)
    principal_tenant = abs(pred.tenant) % 6
    # two poisoned rows, one per query row: a FOREIGN tenant, maximally
    # eligible on every other clause, embedding aligned with the query so
    # its dot score dwarfs every legitimate row — if any structural gate or
    # mask leaked, it would top both k-lists
    qn = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-6)
    emb = np.concatenate([emb, 100.0 * qn.astype(np.float32)])
    tenant = np.concatenate(
        [tenant, np.full(2, (principal_tenant + 1) % 6, np.int32)])
    ts = np.concatenate([ts, np.full(2, 600, np.int32)])
    cat = np.concatenate([cat, cat[:2]])
    acl = np.concatenate([acl, np.full(2, 0xFFFFFFFF, np.uint32)])
    n += 2
    # tenant placement packs each tenant's rows into its owning shard's
    # contiguous region — size regions for the FULLEST shard, not the mean
    cap = S * (int(np.bincount(tenant % S, minlength=S).max()) + 1)
    mesh = make_mesh((S,), ("data",))
    db = RagDB(StoreConfig(capacity=cap, dim=8, metric="dot"), mesh=mesh,
               shard_axes=("data",), placement="tenant")
    db.ingest(DocBatch(emb=jnp.asarray(emb), tenant=jnp.asarray(tenant),
                       category=jnp.asarray(cat), updated_at=jnp.asarray(ts),
                       acl=jnp.asarray(acl, jnp.uint32),
                       doc_id=jnp.arange(n, dtype=jnp.int32)))
    principal = Principal(tenant_id=principal_tenant, group_bits=pred.acl_bits)
    res = (db.session(principal).search(q, normalize=False)
           .newer_than(pred.min_ts).limit(k).using("sharded").run())

    snap = db.log.snapshot()
    snap_tenant = np.asarray(snap["tenant"])
    for b in range(2):
        got = res.slots[b][res.slots[b] >= 0]
        assert (snap_tenant[got] == principal_tenant).all(), \
            "poisoned foreign-tenant row surfaced through the sharded engine"
        assert (res.scores[b] < 50.0).all(), "poisoned score leaked"
    # (a) the per-shard audit: ONLY the owning shard scanned its region
    owner = principal_tenant % S
    want_rows = [cap // S if s == owner else 0 for s in range(S)]
    assert db.stats.shard_rows_scanned == want_rows, \
        (db.stats.shard_rows_scanned, want_rows)
    # (c) bit-identity with the reference engine on the same snapshot
    lowered = Predicate(tenant=principal_tenant, min_ts=pred.min_ts,
                        acl_bits=pred.acl_bits)
    s_ref, i_ref = unified_query_ref(snap, jnp.asarray(q),
                                     lowered.as_array(), k)
    assert (np.asarray(i_ref) == res.slots).all()
    assert (np.asarray(s_ref) == res.scores).all()


SEED_GRID = list(range(40))

if HAVE_HYPOTHESIS:
    # independent field draws so hypothesis can mutate/shrink each clause
    # (the seed grid below is only the hypothesis-absent fallback)
    corpus_st = st.integers(min_value=4, max_value=300).flatmap(
        lambda n: st.tuples(
            st.just(n),
            st.integers(min_value=0, max_value=2**32 - 1),  # numpy seed
            st.integers(min_value=-2, max_value=5),          # tenant pred
            st.integers(min_value=0, max_value=500),         # min_ts
            st.integers(min_value=1, max_value=0xFFFFFFFF),  # cat mask
            st.integers(min_value=1, max_value=0xFFFFFFFF),  # acl bits
            st.integers(min_value=1, max_value=12),          # k
        ))

    @given(corpus_st)
    @settings(max_examples=40, deadline=None)
    def test_no_leak_and_topk_sound(args):
        _check_no_leak_and_topk(args)

    @given(corpus_st)
    @settings(max_examples=15, deadline=None)
    def test_pallas_kernel_same_contract(args):
        _check_pallas_same_contract(args)

    @given(corpus_st)
    @settings(max_examples=15, deadline=None)
    def test_session_front_door_property(args):
        _check_session_front_door(args)

    @given(corpus_st)
    @settings(max_examples=15, deadline=None)
    def test_scheduler_isolation_property(args):
        _check_scheduler_isolation(args)

    @given(corpus_st)
    @settings(max_examples=15, deadline=None)
    def test_chaos_isolation_property(args):
        _check_chaos_isolation(args)

    @given(corpus_st)
    @settings(max_examples=10, deadline=None)
    def test_sharded_affine_isolation_property(args):
        _check_sharded_affine_isolation(args)
else:
    @pytest.mark.parametrize("seed", SEED_GRID)
    def test_no_leak_and_topk_sound(seed):
        _check_no_leak_and_topk(_args_from_seed(seed))

    @pytest.mark.parametrize("seed", SEED_GRID[:15])
    def test_pallas_kernel_same_contract(seed):
        _check_pallas_same_contract(_args_from_seed(seed))

    @pytest.mark.parametrize("seed", SEED_GRID[:15])
    def test_session_front_door_property(seed):
        _check_session_front_door(_args_from_seed(seed))

    @pytest.mark.parametrize("seed", SEED_GRID[:15])
    def test_scheduler_isolation_property(seed):
        _check_scheduler_isolation(_args_from_seed(seed))

    @pytest.mark.parametrize("seed", SEED_GRID[:15])
    def test_chaos_isolation_property(seed):
        _check_chaos_isolation(_args_from_seed(seed))

    @pytest.mark.parametrize("seed", SEED_GRID[:10])
    def test_sharded_affine_isolation_property(seed):
        _check_sharded_affine_isolation(_args_from_seed(seed))
