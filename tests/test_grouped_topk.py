"""The fused multi-predicate grouped scan: scan once, answer every group.

Acceptance contracts (ISSUE 4):
  * the grouped arena-scan kernel (interpret mode) is BIT-identical to the
    jnp ref, which is BIT-identical to the per-group loop it replaces —
    across bucket boundaries and G in {1, 2, 7, 16};
  * the fused executor path (`db.execute` with planner fusion) returns
    scores/slots/tiers bit-identical to the per-group loop, while streaming
    the arena ONCE (`rows_scanned == N`, not G*N) in ONE device program;
  * CROSS-GROUP LEAKAGE IMPOSSIBILITY: a row failing group g's predicate can
    never appear in a g-row's k-list, even when it passes another group's
    predicate in the same fused scan — the kernel-level multi-tenant
    isolation claim, attacked adversarially on a seed grid;
  * `planner.fuse_batch` fuses exactly the exact-engine groups sharing a
    fuse key, and `fuse_min_groups` disables it.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RagDB, fuse_batch
from repro.api import executor as executor_mod
from repro.api.plan import LogicalPlan, PhysicalPlan
from repro.api.planner import CostModel, PlannerConfig
from repro.core import (Predicate, Principal, StoreConfig,
                        unified_query_grouped, unified_query_ref)
from repro.core.query import BLOCK_ALL, stack_predicates
from repro.data.corpus import DAY_S, CorpusConfig, make_corpus
from repro.kernels.arena_scan.ops import arena_scan

pytestmark = [pytest.mark.kernels]

GROUP_COUNTS = (1, 2, 7, 16)


def _arena(rng, n, d=16, n_tenants=6):
    return {
        "emb": jnp.asarray(rng.standard_normal((n, d)).astype(np.float32)),
        "tenant": jnp.asarray(rng.integers(-1, n_tenants, n, dtype=np.int32)),
        "updated_at": jnp.asarray(rng.integers(0, 1000, n, dtype=np.int32)),
        "category": jnp.asarray(rng.integers(0, 8, n, dtype=np.int32)),
        "acl": jnp.asarray(rng.integers(1, 16, n, dtype=np.int64)
                           .astype(np.uint32)),
    }


def _preds(rng, g):
    return [Predicate(tenant=int(rng.integers(-2, 6)),
                      min_ts=int(rng.integers(0, 600)),
                      cat_mask=int(rng.integers(1, 2 ** 32)),
                      acl_bits=int(rng.integers(1, 16)))
            for _ in range(g)]


# ---------------------------------------------------------------------------
# kernel / ref / per-group loop bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,N,D,k,blk_n", [
    (8, 1000, 96, 10, 512),    # N not a block multiple -> padding path
    (3, 513, 64, 8, 256),      # odd everything
    (16, 2048, 128, 5, 512),
    (1, 64, 8, 4, 64),         # tiny arena, B=1
])
@pytest.mark.parametrize("G", GROUP_COUNTS)
def test_kernel_bit_identical_to_ref(B, N, D, k, blk_n, G, rng):
    """Pallas kernel body (interpret mode on CPU) vs jnp ref: every score
    and slot bit-equal, for every group count."""
    store = _arena(rng, N, D)
    q = rng.standard_normal((B, D)).astype(np.float32)
    preds = stack_predicates(_preds(rng, G))
    gids = rng.integers(0, G, B).astype(np.int32)
    args = (q, store["emb"], store["tenant"], store["updated_at"],
            store["category"], store["acl"], gids, preds, k)
    s_r, i_r = arena_scan(*args, use_kernel=False)
    s_k, i_k = arena_scan(*args, use_kernel=True, interpret=True,
                          blk_n=blk_n)
    assert (np.asarray(s_r) == np.asarray(s_k)).all()
    assert (np.asarray(i_r) == np.asarray(i_k)).all()


@pytest.mark.parametrize("G", GROUP_COUNTS)
def test_grouped_ref_bit_identical_to_pergroup_loop(G, rng):
    """The fused scan is a pure batching transform: per query row it returns
    exactly what the per-group exact scan returns for that row's predicate."""
    store = _arena(rng, 700, 24)
    B, k = 9, 6
    q = rng.standard_normal((B, 24)).astype(np.float32)
    preds = _preds(rng, G)
    gids = rng.integers(0, G, B).astype(np.int32)
    s_g, i_g = unified_query_grouped(store, jnp.asarray(q), gids, preds, k)
    s_g, i_g = np.asarray(s_g), np.asarray(i_g)
    for b in range(B):
        s1, i1 = unified_query_ref(store, jnp.asarray(q[b:b + 1]),
                                   preds[int(gids[b])].as_array(), k)
        assert (np.asarray(s1)[0] == s_g[b]).all()
        assert (np.asarray(i1)[0] == i_g[b]).all()


def test_blocker_padding_groups_mask_everything(rng):
    """pow2 G-padding uses BLOCK_ALL rows: a blocker group returns nothing,
    and its presence cannot perturb real groups (shape-reuse safety)."""
    store = _arena(rng, 300, 16)
    q = rng.standard_normal((4, 16)).astype(np.float32)
    preds = _preds(rng, 3)
    gids = np.asarray([0, 1, 2, 0], np.int32)
    s0, i0 = unified_query_grouped(store, jnp.asarray(q), gids, preds, 5)
    s1, i1 = unified_query_grouped(store, jnp.asarray(q), gids,
                                   preds + [BLOCK_ALL], 5)
    assert (np.asarray(s0) == np.asarray(s1)).all()
    assert (np.asarray(i0) == np.asarray(i1)).all()
    # a row pointed AT the blocker group sees an empty arena
    s2, i2 = unified_query_grouped(store, jnp.asarray(q),
                                   np.asarray([3, 3, 3, 3], np.int32),
                                   preds + [BLOCK_ALL], 5)
    assert (np.asarray(i2) == -1).all()


# ---------------------------------------------------------------------------
# fused executor path: bit-identity + the G*N -> N bandwidth audit
# ---------------------------------------------------------------------------

def _db(tiered: bool):
    ccfg = CorpusConfig(n_docs=1200, dim=16, n_tenants=16, n_categories=4)
    scfg = StoreConfig(capacity=2048, dim=16)
    if tiered:
        db = RagDB(scfg, warm_cfg=scfg, hot_window_s=90 * DAY_S,
                   now_ts=ccfg.now_ts, result_cache_size=0)
    else:
        db = RagDB(scfg, result_cache_size=0)
    db.ingest(make_corpus(ccfg))
    return db, ccfg


def _plans(db, ccfg, rng, G, B_total, k=5):
    """B_total query rows spread unevenly over G tenant groups (so fused
    row spans cross bucket boundaries)."""
    plans = []
    for i in range(B_total):
        sess = db.session(Principal(tenant_id=i % G, group_bits=0xFFFFFFFF))
        q = rng.standard_normal(ccfg.dim).astype(np.float32)
        plans.append(sess.search(q).limit(k).plan())
    return plans


@pytest.mark.parametrize("G", GROUP_COUNTS)
@pytest.mark.parametrize("B_total", [7, 8, 9])   # bucket boundary 8
def test_fused_execute_bit_identical_and_scans_once(G, B_total, rng):
    db, ccfg = _db(tiered=False)
    G = min(G, B_total)
    arena = db.log.snapshot()["emb"].shape[0]

    rng_a = np.random.default_rng(11)
    plans_f = _plans(db, ccfg, rng_a, G, B_total)
    rows0, calls0, scans0 = (db.stats.rows_scanned, db.stats.device_calls,
                             db.stats.fused_scans)
    fs, fi, ft = db.execute(plans_f, use_cache=False)
    fused_rows = db.stats.rows_scanned - rows0
    fused_calls = db.stats.device_calls - calls0

    db.planner_cfg = dataclasses.replace(db.planner_cfg,
                                         fuse_min_groups=1 << 30)
    rng_b = np.random.default_rng(11)
    plans_l = _plans(db, ccfg, rng_b, G, B_total)
    rows1, calls1 = db.stats.rows_scanned, db.stats.device_calls
    ls, li, lt = db.execute(plans_l, use_cache=False)
    db.planner_cfg = PlannerConfig()

    assert (fs == ls).all() and (fi == li).all() and (ft == lt).all()
    assert db.stats.rows_scanned - rows1 == G * arena        # the loop: G*N
    assert db.stats.device_calls - calls1 == G
    if G >= 2:
        assert fused_rows == arena, "fused call must stream the arena ONCE"
        assert fused_calls == 1
        assert db.stats.fused_scans == scans0 + 1
    else:
        assert fused_rows == arena and fused_calls == 1      # nothing to fuse


def test_fused_execute_tiered_merge_bit_identical(rng):
    """hot+warm groups fuse too: the hot scan fuses, the per-group warm
    probes and merges stay exact — results identical to the loop."""
    db, ccfg = _db(tiered=True)
    rng_a = np.random.default_rng(5)
    plans_f = _plans(db, ccfg, rng_a, 3, 8)
    assert all(p.route == "hot+warm" for p in plans_f)
    warm0 = db.stats.warm_queries
    fs, fi, ft = db.execute(plans_f, use_cache=False)
    assert db.stats.warm_queries - warm0 == 8     # every row probed warm
    db.planner_cfg = dataclasses.replace(db.planner_cfg,
                                         fuse_min_groups=1 << 30)
    rng_b = np.random.default_rng(5)
    ls, li, lt = db.execute(_plans(db, ccfg, rng_b, 3, 8), use_cache=False)
    db.planner_cfg = PlannerConfig()
    assert (fs == ls).all() and (fi == li).all() and (ft == lt).all()
    assert (ft == 1).any(), "warm tier must contribute rows to the merge"


# ---------------------------------------------------------------------------
# cross-group leakage impossibility (seed grid, adversarial)
# ---------------------------------------------------------------------------

def _oracle_mask(store, pred):
    tenant = np.asarray(store["tenant"])
    ts = np.asarray(store["updated_at"])
    cat = np.asarray(store["category"])
    acl = np.asarray(store["acl"])
    mask = (tenant >= 0) & (ts >= pred.min_ts)
    if pred.tenant != -2:
        mask &= tenant == pred.tenant
    mask &= ((np.uint64(1) << (cat.astype(np.uint64) & np.uint64(31)))
             & np.uint64(pred.cat_mask)) != 0
    mask &= (acl & np.uint32(pred.acl_bits)) != 0
    return mask


@pytest.mark.parametrize("seed", range(25))
@pytest.mark.parametrize("use_kernel", [False, True])
def test_cross_group_leakage_impossible(seed, use_kernel):
    """For ANY corpus and ANY stacked predicate set: no row returned to a
    g-row violates group g's predicate — even rows that PASS another group's
    predicate in the same fused scan (every group here shares the arena, so
    cross-qualifying rows are abundant by construction)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(64, 400))
    G = int(rng.integers(2, 9))
    B = int(rng.integers(G, 2 * G + 4))
    k = int(rng.integers(1, 12))
    store = _arena(rng, n)
    # adversarial predicate set: per-tenant groups (every live row qualifies
    # SOMEWHERE, so any leak has a donor group) + random extra clauses
    preds = [Predicate(tenant=g % 6, min_ts=int(rng.integers(0, 400)),
                       acl_bits=int(rng.integers(1, 16)))
             for g in range(G)]
    gids = rng.integers(0, G, B).astype(np.int32)
    q = rng.standard_normal((B, 16)).astype(np.float32)
    s, slots = arena_scan(q, store["emb"], store["tenant"],
                          store["updated_at"], store["category"],
                          store["acl"], gids, stack_predicates(preds), k,
                          use_kernel=use_kernel,
                          interpret=use_kernel or None, blk_n=64)
    slots = np.asarray(slots)
    masks = [_oracle_mask(store, p) for p in preds]
    for b in range(B):
        got = slots[b][slots[b] >= 0]
        assert masks[int(gids[b])][got].all(), (
            f"LEAK: row {b} (group {int(gids[b])}) returned a slot that "
            f"violates its own group's predicate")
        # exactly min(k, qualifying) rows returned — no under-fill either
        assert len(got) == min(k, int(masks[int(gids[b])].sum()))


# ---------------------------------------------------------------------------
# pow2 padding: blocker lanes carry k=0 semantics (regression, ISSUE 5)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("G,B", [
    (3, 7),    # rows pad 7 -> 8, groups pad 3 -> 4 (one blocker lane)
    (4, 7),    # groups already pow2: a blocker bucket must OPEN for the
               # padding rows (4 -> 8) instead of borrowing group 0
    (3, 8),    # rows exactly at the bucket: no row padding, blocker unused
    (5, 9),    # both sides pad across a boundary
])
def test_padding_rows_ride_blocker_lanes(G, B, rng):
    """Bucket-padding query rows used to inherit group 0's predicate (and
    its k-list): they scored real rows under a real group's predicate and
    allocated k result rows each. They must instead point at a BLOCK_ALL
    blocker lane — k=0 semantics: the executor asserts their k-lists come
    back empty, `ExecStats.padded_groups` audits the lanes, and the real
    rows' results are bit-identical with and without the padding."""
    from repro.api.executor import run_grouped_fused
    store = _arena(rng, 640, 16)
    snap = dict(store)
    q = rng.standard_normal((B, 16)).astype(np.float32)
    uniq = _preds(rng, G)
    preds = [uniq[i % G] for i in range(B)]
    st_pad, st_raw = executor_mod.ExecStats(), executor_mod.ExecStats()
    shapes = executor_mod.CompiledShapes()
    s_p, i_p, _ = run_grouped_fused(snap, q, preds, 5, stats=st_pad,
                                    shapes=shapes)   # bucketed launch
    s_r, i_r, _ = run_grouped_fused(snap, q, preds, 5, stats=st_raw)
    assert (s_p == s_r).all() and (i_p == i_r).all()
    g_uniq = len(set(preds))
    bucket = executor_mod.bucket_rows(B)
    if bucket > B:
        # padding rows exist: at least one blocker lane must exist too,
        # even when the group count was already a power of two
        assert st_pad.padded_groups >= 1
        assert st_pad.padded_rows == bucket - B
    g_bucket = executor_mod.bucket_rows(
        g_uniq + (1 if bucket > B and
                  executor_mod.bucket_rows(g_uniq) == g_uniq else 0))
    assert st_pad.padded_groups == g_bucket - g_uniq
    # the unbucketed launch still pow2-pads the predicate stack only
    assert st_raw.padded_rows == 0


def test_blocker_lane_rows_allocate_no_results(rng):
    """Direct audit of the finish-time assertion: a launch whose padding
    rows point at the blocker lane returns all-empty k-lists for them."""
    from repro.api.executor import (CompiledShapes, ExecStats, _finish_hot,
                                    _launch_grouped)
    store = _arena(rng, 512, 16)
    q = rng.standard_normal((5, 16)).astype(np.float32)   # pads to 8
    preds = _preds(rng, 4)                                # pow2 already
    gids = np.asarray([0, 1, 2, 3, 0], np.int32)
    hot = _launch_grouped(dict(store), q, gids, preds, 6, "ref",
                          stats=ExecStats(), shapes=CompiledShapes())
    s, sl = _finish_hot(hot)    # would assert on a blocker-lane leak
    assert sl.shape[0] == 8
    assert (sl[5:] == -1).all()
    assert (sl[:5] >= -1).any()

def _plan(t=0, k=5, engine="ref", route="hot", n_rows=1024):
    lp = LogicalPlan(tenant=t, k=k)
    return PhysicalPlan(logical=lp, pred=lp.predicate(), engine=engine,
                        engine_reason="", route=route, route_reason="",
                        n_rows=n_rows)


def test_fuse_batch_rules():
    # 3 exact groups sharing (k, engine, route): one fused unit
    units = fuse_batch([_plan(0), _plan(1), _plan(2)])
    assert [u.fused for u in units] == [True]
    assert len(units[0].plans) == 3
    # different k never fuses together
    units = fuse_batch([_plan(0, k=5), _plan(1, k=5), _plan(2, k=7)])
    assert sorted((u.fused, len(u.plans)) for u in units) == [
        (False, 1), (True, 2)]
    # different route never fuses together
    units = fuse_batch([_plan(0, route="hot"), _plan(1, route="hot+warm")])
    assert all(not u.fused for u in units)
    # ivf / sharded stay on their engines
    units = fuse_batch([_plan(0, engine="ivf"), _plan(1, engine="ivf"),
                        _plan(2), _plan(3)])
    flags = [(u.fused, u.plans[0].engine) for u in units]
    assert (False, "ivf") in flags and (True, "ref") in flags
    # fuse_min_groups disables
    units = fuse_batch([_plan(0), _plan(1)],
                       cfg=PlannerConfig(fuse_min_groups=3))
    assert all(not u.fused and "fuse_min_groups" in u.reason for u in units)
    # single group: nothing to fuse
    assert [u.fused for u in fuse_batch([_plan(0)])] == [False]


def test_fuse_batch_priced_by_cost_model():
    cm = CostModel(curves=(("ref", ((1 << 10, 1.0), (1 << 20, 1000.0))),))
    units = fuse_batch([_plan(0), _plan(1)],
                       cfg=PlannerConfig(cost_model=cm))
    assert units[0].fused and "cost model" in units[0].reason
    assert "2 looped scans" in units[0].reason


def test_explain_surfaces_fusion(rng):
    db, ccfg = _db(tiered=False)
    plans = _plans(db, ccfg, rng, 3, 6)
    assert "fusion:    eligible" in plans[0].explain()
    db.execute(plans, use_cache=False)
    text = db.explain()
    assert "grouped scan: fused 3 groups -> 1 scans" in text
    ivf_plan = _plan(engine="ivf")
    assert "not eligible" in ivf_plan.explain()
