"""Arena-scan conformance matrix — ONE grid proving every scan family.

All four scan families (filtered, grouped, ivf, hybrid) are `ScanSpec`
presets of `repro.kernels.arena_scan`'s one entry; this file is the
framework's acceptance contract (ISSUE 7):

  * ENGINE CONFORMANCE: for every (family x shape bucket x page size x
    group count) cell, the dense jnp oracle, the streaming jnp scan, the
    Pallas kernel body (interpret mode on CPU), and BOTH paged variants
    (scan tiled at the page, kernel on double-buffered DMA) return
    bit-equal scores AND slots. The grid includes arenas larger than one
    page (N > page_rows -> multi-page DMA loop), N not a tile multiple
    (dead-row padding path), G at pow2 pad boundaries (3 -> blocker lane,
    4 -> exact), and the historical wsum FMA-divergence shapes
    (5,700,48) / (8,1024,128) at qt in {4, 16} that ISSUE 7 turned green;
  * LEAKAGE IMPOSSIBILITY holds in every cell: a returned slot always
    satisfies ITS group's predicate under an independent numpy oracle —
    the multi-tenant isolation claim, per family and per regime;
  * AUDIT CONFORMANCE: `rows_scanned` / `terms_scanned` report the same
    arena traffic for paged and resident launches (paging changes the DMA
    schedule, never the rows scored), and paged/resident launches occupy
    DISTINCT compiled-shape slots (different grid -> different program);
  * PLAN CONFORMANCE: a planner-stamped paged plan (PlannerConfig
    .paged_min_rows) executes bit-identical to its resident twin through
    `execute_plans`, increments `ExecStats.paged_scans`, and renders the
    "paging:" EXPLAIN line.

The per-family regression grids (test_kernels / test_grouped_topk /
test_hybrid / test_ivf_engine) stay as deep per-family coverage; this
matrix is the single cross-family gate CI runs on every push.
"""
import dataclasses
import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import executor as executor_mod
from repro.api.executor import (CompiledShapes, ExecStats, _finish_hot,
                                _launch_hybrid, run_grouped_fused)
from repro.api.plan import LogicalPlan
from repro.api.planner import PlannerConfig, compile_plan
from repro.core.query import (Predicate, stack_predicates, unified_query,
                              unified_query_ref)
from repro.kernels.arena_scan import ops as scan_ops
from repro.kernels.arena_scan.kernel import arena_scan_pallas
from repro.kernels.arena_scan.ops import (_packed_meta, _pad_axis0,
                                          arena_scan, default_blk_b, pad_d128)
from repro.kernels.arena_scan.ref import (arena_scan_ref, arena_scan_scan_ref,
                                          rrf_fuse)
from repro.kernels.arena_scan.stages import ScanSpec, hybrid_spec

pytestmark = [pytest.mark.kernels, pytest.mark.slow]

W_DENSE, W_LEX = 0.8, 1.7    # the historical FMA-divergence weights
V, T_LANES = 64, 6

IVF = ScanSpec(slot_lane=True)

#: The kernel-level oracles as one program each, as a family ran them.
oracle = jax.jit(arena_scan_ref, static_argnames=("k", "spec"))
scan_oracle = jax.jit(arena_scan_scan_ref, static_argnames=("k", "blk_n",
                                                            "spec"))


@functools.partial(jax.jit, static_argnames=("k", "mode", "w_dense",
                                             "w_lex", "rrf_c"))
def hybrid_oracle(q, emb, meta, terms, lexnorm, gids, preds, qterms, qidf,
                  k: int, mode: str = "wsum", w_dense: float = 1.0,
                  w_lex: float = 1.0, rrf_c: float = 60.0):
    """The dense oracle at a fusion mode's spec: wsum folds its weights into
    q and qidf (pinning rule 1), rrf fuses the two lists after the scan.
    ``meta``, ``terms`` and ``lexnorm`` are lane-major."""
    if mode == "wsum":
        q, qidf = q * jnp.float32(w_dense), qidf * jnp.float32(w_lex)
    out = arena_scan_ref(q, emb, meta, gids, preds, k, spec=hybrid_spec(mode),
                         lex=(terms, lexnorm, qterms, qidf))
    return out if mode == "wsum" else rrf_fuse(*out, k, rrf_c)


# ---------------------------------------------------------------------------
# shared fixtures: one arena schema serves every family
# ---------------------------------------------------------------------------

def _arena(rng, n, d, n_tenants=5, vocab=V, lanes=T_LANES, pool=None):
    """A random arena; with ``pool`` its term lanes draw from those ids
    (and empty lanes) instead of the whole vocabulary."""
    if pool is None:
        terms = rng.integers(-1, vocab, (n, lanes)).astype(np.int32)
    else:
        terms = rng.choice(np.append(pool, -1), (n, lanes)).astype(np.int32)
    lexnorm = np.where(terms >= 0,
                       (rng.random((n, lanes)) * 2).astype(np.float32),
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
        "idf": jnp.asarray((rng.random(vocab) * 5).astype(np.float32)),
    }


def _oracle_mask(store, pred: Predicate) -> np.ndarray:
    """Independent numpy WHERE clause (no jax) for leakage assertions."""
    tenant = np.asarray(store["tenant"])
    ok = tenant >= 0
    if pred.tenant != -2:
        ok &= tenant == pred.tenant
    ok &= np.asarray(store["updated_at"]) >= pred.min_ts
    ok &= (np.uint32(pred.cat_mask)
           >> np.asarray(store["category"]).astype(np.uint32)) & 1 != 0
    ok &= (np.asarray(store["acl"]) & np.uint32(pred.acl_bits)) != 0
    return ok


def _assert_no_leak(store, preds, gids, slots):
    """Every returned slot must satisfy ITS group's predicate."""
    masks = [_oracle_mask(store, p) for p in preds]
    slots = np.asarray(slots)
    for b in range(slots.shape[0]):
        real = slots[b][slots[b] >= 0]
        assert masks[int(gids[b])][real].all(), (
            f"row {b} (group {int(gids[b])}) leaked slots "
            f"{real[~masks[int(gids[b])][real]]}")


def _assert_all_equal(outs: dict):
    """Bit-equality across every engine lane, named for the failure."""
    names = list(outs)
    s0, i0 = (np.asarray(a) for a in outs[names[0]])
    for name in names[1:]:
        s, i = (np.asarray(a) for a in outs[name])
        assert (s == s0).all(), f"{name} scores != {names[0]}"
        assert (i == i0).all(), f"{name} slots != {names[0]}"


# ---------------------------------------------------------------------------
# per-family engine lanes: oracle / scan / kernel x resident / paged
# ---------------------------------------------------------------------------

def _lanes_filtered(rng, store, B, N, D, k, G, qt, page):
    """Single-predicate family. The bit oracle is the G=1 arena-scan dense
    oracle; the core `unified_query_ref` is a DIFFERENT XLA program (its
    own matmul + mask fusion) and is held to allclose + same winner set,
    not bits — the framework's bit contract covers its own engines."""
    pred = Predicate(tenant=1, min_ts=100)
    q = rng.standard_normal((B, D)).astype(np.float32)
    meta = _packed_meta(store["tenant"], store["updated_at"],
                        store["category"], store["acl"])
    outs = {
        "oracle": oracle(jnp.asarray(q), store["emb"], meta,
                         jnp.zeros(B, jnp.int32), pred.as_array()[None, :],
                         k),
        "scan": unified_query(store, jnp.asarray(q), pred, k, engine="ref",
                              page_rows=N),   # one tile = classic scan
        "kernel": unified_query(store, jnp.asarray(q), pred, k,
                                engine="pallas"),
    }
    if page is not None:
        outs["scan-paged"] = unified_query(store, jnp.asarray(q), pred, k,
                                           engine="ref", page_rows=page)
        outs["kernel-paged"] = unified_query(store, jnp.asarray(q), pred, k,
                                             engine="pallas", page_rows=page)
    s_core, i_core = unified_query_ref(store, jnp.asarray(q),
                                       pred.as_array(), k)
    s_o, i_o = outs["oracle"]
    assert np.allclose(np.asarray(s_core), np.asarray(s_o), atol=1e-5)
    assert (np.asarray(i_core) == np.asarray(i_o)).all()
    return outs, [pred], np.zeros(B, np.int32)


def _lanes_grouped(rng, store, B, N, D, k, G, qt, page):
    q = rng.standard_normal((B, D)).astype(np.float32)
    gids = rng.integers(0, G, B).astype(np.int32)
    preds = [Predicate(tenant=i % 3, min_ts=100) for i in range(G)]
    pa = stack_predicates(preds)
    meta = _packed_meta(store["tenant"], store["updated_at"],
                        store["category"], store["acl"])

    def call(**kw):
        return arena_scan(q, store["emb"], store["tenant"],
                          store["updated_at"], store["category"],
                          store["acl"], gids, pa, k, **kw)

    outs = {
        "oracle": oracle(jnp.asarray(q), store["emb"], meta,
                         jnp.asarray(gids), pa, k),
        "scan": call(use_kernel=False),
        "kernel": call(use_kernel=True, interpret=True),
    }
    if page is not None:
        outs["scan-paged"] = call(use_kernel=False, page_rows=page)
        outs["kernel-paged"] = call(use_kernel=True, interpret=True,
                                    page_rows=page)
    return outs, preds, gids


def _lanes_ivf(rng, store, B, N, D, k, G, qt, page):
    """ivf probes a gathered candidate set with a slot lane; ~1/8 of the
    candidates are dead member-table padding (slot -1), exercising the
    dead-slot path in every regime. N here is the candidate count P."""
    pred = Predicate(tenant=1, min_ts=100)
    q = rng.standard_normal((B, D)).astype(np.float32)
    slots = rng.permutation(4 * N)[:N].astype(np.int32)
    dead = rng.random(N) < 0.125
    slots[dead] = -1
    meta = np.stack([np.asarray(store["tenant"]),
                     np.asarray(store["updated_at"]),
                     np.asarray(store["category"]),
                     np.asarray(store["acl"]).view(np.int32),
                     slots], axis=0).astype(np.int32)     # lane-major (5, P)
    meta[:, dead] = np.asarray([-1, 0, 0, 0, -1])[:, None]
    cand_emb = np.asarray(store["emb"]).copy()
    cand_emb[dead] = 0.0
    cand_emb, meta = jnp.asarray(cand_emb), jnp.asarray(meta)
    pa = pred.as_array()[None, :]
    gids = jnp.zeros(B, jnp.int32)

    qp, embp = pad_d128(jnp.asarray(q), cand_emb)
    qp = _pad_axis0(qp, 8, 0)

    def kernel(**kw):
        s, i, _ = arena_scan_pallas(qp, embp, meta,
                                    jnp.zeros((8, 1), jnp.int32), pa, k,
                                    spec=IVF, blk_b=8, interpret=True, **kw)
        return s[:B], i[:B]

    outs = {
        "oracle": oracle(jnp.asarray(q), cand_emb, meta, gids, pa, k,
                         spec=IVF),
        "scan": scan_oracle(jnp.asarray(q), cand_emb, meta, gids, pa, k,
                            blk_n=N, spec=IVF),
        "kernel": kernel(blk_n=256),
    }
    if page is not None:
        outs["scan-paged"] = scan_oracle(jnp.asarray(q), cand_emb, meta,
                                         gids, pa, k, blk_n=page, spec=IVF)
        outs["kernel-paged"] = kernel(blk_n=256, page_rows=page)

    # slot-lane leakage: returned ARENA slots must come from live candidates
    # that pass the predicate
    cand_ok = _oracle_mask(store, pred) & ~dead
    legal = set(slots[cand_ok].tolist())
    for name, (_, i) in outs.items():
        for slot in np.asarray(i).ravel():
            assert slot == -1 or int(slot) in legal, (
                f"{name} returned slot {slot} outside the qualifying "
                f"candidate set")
    return outs, None, None


def _lanes_hybrid(mode, pool=None):
    """Hybrid lanes; with ``pool`` the query terms draw from those ids."""
    def lanes(rng, store, B, N, D, k, G, qt, page):
        q = rng.standard_normal((B, D)).astype(np.float32)
        if pool is None:
            qterms = rng.integers(-1, V, (B, qt)).astype(np.int32)
            qterms[:, 0] = rng.integers(0, V, B)  # at least one real term
        else:
            qterms = rng.choice(np.append(pool, -1), (B, qt)).astype(np.int32)
            qterms[:, 0] = rng.choice(pool, B)
        gids = rng.integers(0, G, B).astype(np.int32)
        preds = [Predicate(tenant=i % 3, min_ts=100) for i in range(G)]
        pa = stack_predicates(preds)
        kw = dict(w_dense=W_DENSE, w_lex=W_LEX)

        def call(**extra):
            return arena_scan(q, store["emb"], store["tenant"],
                              store["updated_at"], store["category"],
                              store["acl"], gids, pa, k,
                              spec=hybrid_spec(mode), terms=store["terms"],
                              lexnorm=store["lexnorm"], idf=store["idf"],
                              qterms=qterms, **kw, **extra)

        meta = _packed_meta(store["tenant"], store["updated_at"],
                            store["category"], store["acl"])
        qidf = np.where(qterms >= 0,
                        np.asarray(store["idf"])[np.clip(qterms, 0, None)],
                        0.0).astype(np.float32)
        outs = {
            "oracle": hybrid_oracle(jnp.asarray(q), store["emb"], meta,
                                    store["terms"].T, store["lexnorm"].T,
                                    jnp.asarray(gids), pa,
                                    jnp.asarray(qterms), jnp.asarray(qidf),
                                    k, mode=mode, **kw),
            "scan": call(use_kernel=False),
            "kernel": call(use_kernel=True, interpret=True),
        }
        if page is not None:
            outs["scan-paged"] = call(use_kernel=False, page_rows=page)
            outs["kernel-paged"] = call(use_kernel=True, interpret=True,
                                        page_rows=page)
        return outs, preds, gids
    return lanes


FAMILIES = {
    "filtered": _lanes_filtered,
    "grouped": _lanes_grouped,
    "ivf": _lanes_ivf,
    "hybrid-wsum": _lanes_hybrid("wsum"),
    "hybrid-rrf": _lanes_hybrid("rrf"),
}

# (family, B, N, D, k, G, qt, page_rows) — page_rows=None pins the resident
# regime only; page_rows < N exercises a genuine multi-page DMA loop.
CASES = [
    # --- filtered (G=1 by construction) ---
    ("filtered", 1, 64, 8, 4, 1, 0, None),
    ("filtered", 5, 700, 48, 8, 1, 0, 256),     # 3 pages, N % page != 0
    ("filtered", 8, 1024, 128, 10, 1, 0, 512),  # 2 pages, exact multiple
    ("filtered", 3, 513, 64, 8, 1, 0, 128),     # 5 pages, odd N
    # --- grouped (G spans the pow2 pad boundary) ---
    ("grouped", 1, 64, 8, 4, 1, 0, None),
    ("grouped", 8, 1000, 96, 10, 3, 0, 256),    # G=3 -> blocker-padded to 4
    ("grouped", 3, 513, 64, 8, 4, 0, 128),      # G=4 -> exact pow2
    ("grouped", 16, 2048, 128, 5, 7, 0, 512),
    # --- ivf (slot-lane candidates incl. dead member padding) ---
    ("ivf", 8, 512, 64, 8, 1, 0, None),
    ("ivf", 5, 512, 48, 8, 1, 0, 128),          # 4 pages
    ("ivf", 3, 768, 32, 6, 1, 0, 256),          # 3 pages
    # --- hybrid wsum (incl. the historical FMA-divergence shapes) ---
    ("hybrid-wsum", 1, 64, 8, 4, 1, 1, None),
    ("hybrid-wsum", 5, 700, 48, 8, 3, 4, 256),
    ("hybrid-wsum", 8, 1024, 128, 10, 3, 16, 512),
    ("hybrid-wsum", 3, 513, 64, 8, 4, 4, 128),
    # --- hybrid rrf ---
    ("hybrid-rrf", 1, 64, 8, 4, 1, 1, None),
    ("hybrid-rrf", 5, 700, 48, 8, 3, 4, 256),
    ("hybrid-rrf", 8, 1024, 128, 10, 3, 16, 512),
]

IDS = [f"{f}-B{B}-N{N}-D{D}-k{k}-G{G}-qt{qt}-pg{pg}"
       for f, B, N, D, k, G, qt, pg in CASES]


@pytest.mark.parametrize("family,B,N,D,k,G,qt,page", CASES, ids=IDS)
def test_conformance_matrix(family, B, N, D, k, G, qt, page, rng):
    """Every engine lane of every family returns the same bits, and no lane
    can leak a row its group's predicate rejects."""
    store = _arena(rng, N, D)
    outs, preds, gids = FAMILIES[family](rng, store, B, N, D, k, G, qt, page)
    if page is not None:
        assert N > page, "paged cells must cover arena > 1 page"
        assert {"scan-paged", "kernel-paged"} <= outs.keys()
    _assert_all_equal(outs)
    if preds is not None:   # ivf asserts its slot-lane leakage inline
        for name, (_, slots) in outs.items():
            _assert_no_leak(store, preds, gids, slots)


# ---------------------------------------------------------------------------
# the merge gate: a running list merges only the tiles that can enter it
# ---------------------------------------------------------------------------

GATE_N, GATE_D, GATE_K, GATE_T = 2048, 128, 8, 512   # four 512-row tiles


def _gate_case(case, rng):
    """One merge-gate case: (store, q, gids, preds, qterms, the tiles each
    list must merge or None where the count is not known in advance)."""
    store = _arena(rng, GATE_N, GATE_D)
    q = rng.standard_normal((8, GATE_D)).astype(np.float32)
    gids = np.zeros(8, np.int32)
    preds = [Predicate()]
    qterms, merges = None, None
    if case == "ties":
        # every tile repeats the same 64 live rows: after the first tile
        # the list holds 8 copies of each row's best, and a later copy only
        # ties the k-th entry, which loses to the list (strict >)
        emb = np.tile(np.asarray(store["emb"])[:64], (GATE_N // 64, 1))
        store.update(emb=jnp.asarray(emb),
                     tenant=jnp.asarray(rng.integers(0, 5, GATE_N,
                                                     dtype=np.int32)))
        merges = [1]
    elif case == "never-fills":
        # a group two rows satisfy, in the second and the fourth tile: its
        # list never fills, the other tiles are all NEG_INF, and the
        # fourth tile's row (half the second's score) still enters
        ts = np.asarray(store["updated_at"]).copy()
        ts[ts >= 999] = 998
        ts[[700, 1900]] = 999
        emb = np.asarray(store["emb"]).copy()
        emb[1900] = 0.5 * emb[700]
        store.update(updated_at=jnp.asarray(ts), emb=jnp.asarray(emb),
                     tenant=store["tenant"].at[np.asarray([700, 1900])].set(2))
        q = emb[700] * np.arange(1, 9, dtype=np.float32)[:, None]
        preds = [Predicate(tenant=2, min_ts=999)]
        merges = [2]
    elif case == "padded-rows":
        # 5 query rows pad to the 8-row block with zero rows, which tie
        # every arena row at 0.0
        q, gids = q[:5], np.asarray([0, 1, 2, 0, 1], np.int32)
        preds = [Predicate(tenant=g, min_ts=100) for g in range(3)]
    else:
        # rrf: the query term sits in the third tile alone. The bm25 list
        # fills with zeros in the first tile and merges the third; the
        # dense list merges every tile (each is a record for some row)
        terms = np.full((GATE_N, T_LANES), -1, np.int32)
        terms[1100:1110, 0] = 5
        store.update(terms=jnp.asarray(terms),
                     lexnorm=jnp.asarray(np.where(terms >= 0, 1.0, 0.0)
                                         .astype(np.float32)),
                     tenant=jnp.asarray(rng.integers(0, 5, GATE_N,
                                                     dtype=np.int32)))
        qterms = np.full((8, 2), -1, np.int32)
        qterms[:, 0] = 5
        merges = [GATE_N // GATE_T, 2]
    return store, q, gids, preds, qterms, merges


@pytest.mark.parametrize("case", ["ties", "never-fills", "padded-rows",
                                  "rrf-one-list-merges"])
@pytest.mark.parametrize("page", [None, GATE_T], ids=["resident", "paged"])
def test_merge_gate_bits(case, page, rng):
    """The gated kernel body (interpret mode, resident and paged) returns
    the dense oracle's bits where a skipped tile ties the k-th entry, where
    a group's list never fills, where padding rows join the query block,
    and where one of rrf's lists merges a tile the other skips; and its
    merge tally counts the tiles the case lets in."""
    store, q, gids, preds, qterms, merges = _gate_case(case, rng)
    pa = stack_predicates(preds)
    meta = _packed_meta(store["tenant"], store["updated_at"],
                        store["category"], store["acl"])
    cols = (store["emb"], store["tenant"], store["updated_at"],
            store["category"], store["acl"])
    kw = {}
    lex = None
    if qterms is not None:
        kw = dict(spec=hybrid_spec("rrf"), terms=store["terms"],
                  lexnorm=store["lexnorm"], idf=store["idf"], qterms=qterms,
                  lists=True)
        qidf = np.where(qterms >= 0, np.asarray(store["idf"])[
            np.clip(qterms, 0, None)], 0.0).astype(np.float32)
        lex = (store["terms"].T, store["lexnorm"].T, jnp.asarray(qterms),
               jnp.asarray(qidf))
    want = oracle(jnp.asarray(q), store["emb"], meta, jnp.asarray(gids), pa,
                  GATE_K, spec=kw.get("spec", ScanSpec()), lex=lex)
    *got, (merged, tiles) = arena_scan(
        q, *cols, gids, pa, GATE_K, use_kernel=True, interpret=True,
        page_rows=page, tally=True, **kw)
    assert len(got) == len(want)
    for j in range(0, len(want), 2):
        _assert_all_equal({"oracle": want[j:j + 2], "kernel": got[j:j + 2]})
        _assert_no_leak(store, preds, gids, got[j + 1])
    merged = np.asarray(merged)
    n_lists = len(want) // 2
    assert tiles == n_lists * GATE_N // GATE_T          # one 8-row block
    assert merged.shape == (n_lists,)
    assert ((merged >= 1) & (merged <= GATE_N // GATE_T)).all()
    if merges is not None:
        assert merged.tolist() == merges


# ---------------------------------------------------------------------------
# MS MARCO widths: 64 postings lanes, 16 query terms, the 30,522-id
# WordPiece vocabulary of BERT-base
# ---------------------------------------------------------------------------

V_MSMARCO, T_MSMARCO = 30522, 64


@pytest.mark.parametrize("mode", ["wsum", "rrf"])
def test_hybrid_msmarco_widths(mode, rng):
    """At T = 64 lanes and QT = 16 query terms over the whole vocabulary,
    the oracle, the streaming scan and the kernel body (interpret mode),
    resident and on 256-row pages, return the same bits; and no row of a
    tenant no group asks for surfaces, though rows of one hold every query
    term at the largest lane weight."""
    B, N, D, k, G, qt, page = 5, 640, 32, 8, 3, 16, 256
    pool = np.union1d(rng.choice(V_MSMARCO, 46, replace=False),
                      [0, V_MSMARCO - 1]).astype(np.int32)
    store = _arena(rng, N, D, vocab=V_MSMARCO, lanes=T_MSMARCO, pool=pool)
    terms = np.asarray(store["terms"]).copy()
    lexnorm = np.asarray(store["lexnorm"]).copy()
    foreign = np.arange(0, N, 64)                 # tenant 4: no group's
    terms[foreign] = -1
    terms[foreign, :len(pool)] = pool
    lexnorm[foreign] = np.where(terms[foreign] >= 0, 2.0, 0.0)
    store.update(terms=jnp.asarray(terms), lexnorm=jnp.asarray(lexnorm),
                 tenant=store["tenant"].at[foreign].set(4))
    outs, preds, gids = _lanes_hybrid(mode, pool)(rng, store, B, N, D, k, G,
                                                  qt, page)
    assert {"scan-paged", "kernel-paged"} <= outs.keys()
    _assert_all_equal(outs)
    for _, slots in outs.values():
        _assert_no_leak(store, preds, gids, slots)
        assert not np.isin(np.asarray(slots), foreign).any()


@pytest.mark.parametrize("mode", ["wsum", "rrf"])
def test_hybrid_group_padding_bits(mode, rng):
    """A hybrid launch pads its predicate groups to its row bucket with
    blocker lanes, so one program serves every group count of a bucket.
    The group select is an exact 0/1 one-hot: the padded launch returns
    the bits of the unpadded scan, in the kernel body and the scan."""
    N, D, B, G, k, qt = 640, 32, 5, 2, 8, 4
    store = _arena(rng, N, D)
    lex = {"terms": store["terms"], "lexnorm": store["lexnorm"],
           "idf": store["idf"]}
    q = rng.standard_normal((B, D)).astype(np.float32)
    qterms = rng.integers(0, V, (B, qt)).astype(np.int32)
    gids = np.asarray([i % G for i in range(B)], np.int32)
    preds = [Predicate(tenant=i % 3, min_ts=100) for i in range(G)]
    kw = dict(w_dense=W_DENSE, w_lex=W_LEX, rrf_c=60.0)

    stats, shapes = ExecStats(), CompiledShapes()
    s_l, i_l = _finish_hot(_launch_hybrid(dict(store), lex, q, gids, preds,
                                          qterms, k, mode=mode, stats=stats,
                                          shapes=shapes, **kw))
    assert stats.padded_rows == 8 - B
    assert stats.padded_groups == 8 - G          # groups to the row bucket
    qp, gp, pp, _ = executor_mod._pad_group_launch(
        q, gids, preds, k, "hybrid", stats=None, shapes=CompiledShapes(),
        groups_per_row=True)
    qtp = np.concatenate([qterms, np.full((8 - B, qt), -1, np.int32)])
    cols = (store["emb"], store["tenant"], store["updated_at"],
            store["category"], store["acl"])
    kw.update(spec=hybrid_spec(mode), terms=store["terms"],
              lexnorm=store["lexnorm"], idf=store["idf"])
    for use_kernel in (False, True):
        ref = arena_scan(q, *cols, gids, stack_predicates(preds), k,
                         qterms=qterms, use_kernel=use_kernel,
                         interpret=True, **kw)
        pad = arena_scan(qp, *cols, gp, stack_predicates(pp), k,
                         qterms=qtp, use_kernel=use_kernel, interpret=True,
                         **kw)
        _assert_all_equal({"unpadded": ref,
                           "padded": tuple(a[:B] for a in pad),
                           "launch": (s_l[:B], i_l[:B])})
        _assert_no_leak(store, preds, gids, ref[1])


@pytest.mark.parametrize("mode", ["wsum", "rrf"])
@pytest.mark.parametrize("page", [None, 256], ids=["resident", "paged"])
def test_hybrid_bucket_join_bits(mode, page, rng, monkeypatch):
    """Hybrid reads of query-term buckets 1, 4 and 16 from three tenants
    join into ONE launch at QT 16 (`planner.fuse_batch`). A padding term
    adds exactly +0.0 to a row's BM25, so each read gets the bits it gets
    served alone, in the scan and the kernel body, resident and paged; and
    no row of another tenant comes back."""
    N, D, k = 640, 32, 8
    store = _arena(rng, N, D)
    snap = {"terms": store["terms"], "lexnorm": store["lexnorm"],
            "idf": store["idf"]}
    lex = SimpleNamespace(snapshot=lambda: snap,
                          cfg=SimpleNamespace(rrf_c=60.0, doc_terms=T_LANES))
    cfg = (PlannerConfig() if page is None
           else PlannerConfig(paged_min_rows=1, page_rows=page))
    reads = [(0, 1), (1, 4), (2, 16), (0, 3), (1, 11)]    # (tenant, terms)
    q = rng.standard_normal((len(reads), D)).astype(np.float32)
    plans = [compile_plan(
        LogicalPlan(tenant=t, min_ts=100, k=k, q=q[i:i + 1],
                    match_terms=tuple(int(x) for x in
                                      rng.choice(V, n, replace=False)),
                    fusion=mode, w_dense=W_DENSE if mode == "wsum" else 1.0,
                    w_lex=W_LEX if mode == "wsum" else 1.0),
        n_rows=N, hot_window_s=100, now_ts=1000, warm_rows=0, cfg=cfg,
        lex=lex) for i, (t, n) in enumerate(reads)]
    assert {p.lex[1] for p in plans} == {1, 4, 16}
    assert all(p.page_rows == page for p in plans)

    def serve(ps, stats=None):
        return executor_mod.execute_plans(
            dict(store), None, ps, stats=stats, shapes=CompiledShapes(),
            planner_cfg=cfg, lex=lex)[:2]

    tenant = np.asarray(store["tenant"])
    for name, use_kernel in (("scan", False), ("kernel", True)):
        monkeypatch.setattr(
            scan_ops, "default_use_kernel",
            lambda u, use_kernel=use_kernel: use_kernel if u is None else u)
        stats = ExecStats()
        s_j, i_j = serve(plans, stats)
        assert (stats.device_calls, stats.lex_bucket_joins) == (1, 1), name
        for r, p in enumerate(plans):
            s_a, i_a = serve([p])
            _assert_all_equal({f"{name}-alone": (s_a[0], i_a[0]),
                               f"{name}-joined": (s_j[r], i_j[r])})
            real = i_j[r][i_j[r] >= 0]
            assert len(real) and (tenant[real] == reads[r][0]).all(), name
            assert _oracle_mask(store, p.pred)[real].all(), name


@pytest.mark.parametrize("engine,groups_per_row,n_shapes", [
    ("hybrid", True, 5),        # one per row bucket 1, 2, 4, 8, 16
    ("grouped", False, 15),     # every (rows, groups) bucket pair, as before
])
def test_group_launch_shapes_at_batch_16(engine, groups_per_row, n_shapes):
    """The launch shapes a batch of up to 16 reads in 1 to 16 predicate
    groups reaches. A hybrid launch pads its groups to the row bucket, so
    at 2 fusion modes x 5 query-term buckets its warm-up compiles 50
    programs (150 with a shape per groups bucket); dense shapes stay."""
    shapes = set()
    for n in range(1, 17):
        for g in range(1, n + 1):
            q, gids, preds, n_valid = executor_mod._pad_group_launch(
                np.zeros((n, 8), np.float32),
                np.arange(n, dtype=np.int32) % g,
                [Predicate(tenant=t) for t in range(g)], 8, engine,
                stats=None, shapes=CompiledShapes(),
                groups_per_row=groups_per_row)
            assert n_valid == n and len(gids) == q.shape[0]
            shapes.add((q.shape[0], len(preds)))
    assert len(shapes) == n_shapes


def _products_over(jaxpr, width: int) -> list:
    """Precision of every dot_general in ``jaxpr`` (sub-jaxprs included:
    the jitted wrapper, the Pallas kernel body, loop bodies) that contracts
    an axis of ``width``."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (ca, _), _ = eqn.params["dimension_numbers"]
            if any(eqn.invars[0].aval.shape[a] == width for a in ca):
                out.append(eqn.params["precision"])
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _products_over(sub, width)
    return out


@pytest.mark.parametrize("family", ["grouped", "wsum", "rrf"])
@pytest.mark.parametrize("use_kernel", [True, False], ids=["kernel", "scan"])
@pytest.mark.parametrize("page", [None, 2048], ids=["resident", "paged"])
def test_similarity_product_is_exact_f32(family, use_kernel, page):
    """Every product over the embedding width, in the kernel the chip runs
    and in the jnp scan, dense and hybrid (wsum and rrf), resident and
    paged, is pinned to `Precision.HIGHEST`: the configurations state
    exact f32 similarity, and on a TPU any lower precision (HIGH: three
    bf16 passes; DEFAULT: one) changes scores by more than f32 rounding.
    On the CPU every precision gives the same bits, so the bit-equality
    grids above cannot see a lower one; this reads the traced programs."""
    import jax
    N, D, B, G, QT = 4096, 768, 8, 4, 16
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    arena = (sds((B, D), f32), sds((N, D), f32), sds((N,), i32),
             sds((N,), i32), sds((N,), i32), sds((N,), jnp.uint32))
    tail = (sds((B,), i32), sds((G, 4), i32))
    kw = dict(use_kernel=use_kernel, interpret=False, page_rows=page)
    if family == "grouped":
        def scan(*a):
            return arena_scan(*a, 10, **kw)
        args = arena + tail
    else:
        def scan(*a):
            *cols, terms, lexnorm, idf, gids, preds, qterms = a
            return arena_scan(*cols, gids, preds, 10,
                              spec=hybrid_spec(family), terms=terms,
                              lexnorm=lexnorm, idf=idf, qterms=qterms, **kw)
        args = arena + (sds((N, T_MSMARCO), i32), sds((N, T_MSMARCO), f32),
                        sds((V_MSMARCO,), f32)) + tail + (sds((B, QT), i32),)
    precisions = _products_over(jax.make_jaxpr(scan)(*args).jaxpr, D)
    assert precisions, "no similarity product found"
    highest = (jax.lax.Precision.HIGHEST,) * 2
    assert all(p == highest for p in precisions), precisions


# ---------------------------------------------------------------------------
# query-row block: the dense kernel holds the whole batch in one block
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["filtered", "grouped"])
@pytest.mark.parametrize("B", [16, 32])
@pytest.mark.parametrize("page", [None, 256])
def test_query_block_bits(family, B, page, rng):
    """The dense kernel at its default query-row block (the whole padded
    batch, one arena stream) returns the same bits as at 8-row blocks (one
    stream per 8 rows), resident and paged, and leaks no row."""
    N, D, k, G = 1000, 96, 10, 3
    blk_b = default_blk_b(B, ScanSpec())
    assert blk_b == B
    store = _arena(rng, N, D)
    q = rng.standard_normal((B, D)).astype(np.float32)
    cols = (store["emb"], store["tenant"], store["updated_at"],
            store["category"], store["acl"])
    if family == "filtered":
        preds, gids = [Predicate(tenant=1, min_ts=100)], np.zeros(B, np.int32)
    else:
        preds = [Predicate(tenant=i % 3, min_ts=100) for i in range(G)]
        gids = rng.integers(0, G, B).astype(np.int32)

    def call(**kw):
        return arena_scan(q, *cols, gids, stack_predicates(preds), k,
                          use_kernel=True, interpret=True, page_rows=page,
                          **kw)

    outs = {"blk_b=8": call(blk_b=8), f"blk_b={blk_b}": call()}
    _assert_all_equal(outs)
    for _, slots in outs.values():
        _assert_no_leak(store, preds, gids, slots)


# ---------------------------------------------------------------------------
# the one entry: every family launches the same jitted program
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family,B,blk_b,blk_n", [
    ("dense-G1", 5, 8, 512),       # filtered: the padded batch
    ("dense-G4", 200, 128, 512),   # grouped: the batch, capped at 128 rows
    ("wsum", 20, 8, 512),          # lexical: 8-row blocks
    ("rrf", 20, 8, 512),
    ("ivf", 20, 8, 256),           # candidate set: 8 rows, 256-row tiles
])
def test_entry_lowers_as_jit_run(family, B, blk_b, blk_n, rng, monkeypatch):
    """Every family's launch is one program of the entry's `_run`, which
    lowers as the module ``jit__run`` (the name the benchmark's device
    readers find the scans by), at the tiles the family always took."""
    import inspect
    from repro.core.ivf import ivf_probe
    N, D, k = 1024, 16, 4
    store = _arena(rng, N, D)
    q = rng.standard_normal((B, D)).astype(np.float32)
    cols = (store["emb"], store["tenant"], store["updated_at"],
            store["category"], store["acl"])
    run, seen = scan_ops._run, []

    def spy(*args):
        seen.append((inspect.signature(run).bind(*args).arguments,
                     run.lower(*args).as_text()))
        return run.eval_shape(*args)

    monkeypatch.setattr(scan_ops, "_run", spy)
    G = 4 if family == "dense-G4" else 1
    gids = np.arange(B, dtype=np.int32) % G
    preds = stack_predicates([Predicate(tenant=g) for g in range(G)])
    if family.startswith("dense"):
        arena_scan(q, *cols, gids, preds, k, use_kernel=True)
    elif family == "ivf":
        members = jnp.asarray(rng.integers(-1, N, (8, 64)).astype(np.int32))
        ivf_probe(q, store, members, jnp.arange(128, dtype=jnp.int32),
                  np.arange(4, dtype=np.int32), preds[0], k, use_kernel=True)
    else:
        qterms = rng.integers(-1, V, (B, 4)).astype(np.int32)
        arena_scan(q, *cols, gids, preds, k, spec=hybrid_spec(family),
                   terms=store["terms"], lexnorm=store["lexnorm"],
                   idf=store["idf"], qterms=qterms, use_kernel=True)
    (bound, text), = seen
    assert text.splitlines()[0].startswith("module @jit__run"), text[:80]
    assert (bound["blk_b"], bound["blk_n"]) == (blk_b, blk_n)
    assert bound["blk_b"] == default_blk_b(B, bound["spec"])


# ---------------------------------------------------------------------------
# audit conformance: paging changes the DMA schedule, never the audit trail
# ---------------------------------------------------------------------------

def test_rows_scanned_audit_paged_equals_resident(rng):
    """A paged fused grouped scan reports the same `rows_scanned` as its
    resident twin (the arena N, ONCE — not per page, not per group), returns
    the same bits, and occupies a DISTINCT compiled-shape slot."""
    N, D, B, G, k = 1000, 32, 9, 3, 7
    store = _arena(rng, N, D)
    q = rng.standard_normal((B, D)).astype(np.float32)
    uniq = [Predicate(tenant=i % 3, min_ts=100) for i in range(G)]
    preds = [uniq[i % G] for i in range(B)]

    shapes = CompiledShapes()
    st_res, st_pg = ExecStats(), ExecStats()
    s_r, i_r, _ = run_grouped_fused(dict(store), q, preds, k, stats=st_res,
                                    shapes=shapes)
    s_p, i_p, _ = run_grouped_fused(dict(store), q, preds, k, stats=st_pg,
                                    shapes=shapes, page_rows=256)
    assert (np.asarray(s_r) == np.asarray(s_p)).all()
    assert (np.asarray(i_r) == np.asarray(i_p)).all()
    assert st_res.rows_scanned == N
    assert st_pg.rows_scanned == N, "paging must not inflate the row audit"
    assert shapes.misses == 2, (
        "paged and resident launches compile different programs and must "
        "key separate compiled-shape slots")


def test_terms_scanned_audit_paged_equals_resident(rng):
    """The hybrid lexical-bandwidth audit (`terms_scanned` = N * doc term
    lanes) is regime-independent, and the paged launch returns the same
    bits through the executor's launch/finish path."""
    N, D, B, G, k, qt = 768, 16, 6, 3, 5, 4
    store = _arena(rng, N, D)
    lex = {"terms": store["terms"], "lexnorm": store["lexnorm"],
           "idf": store["idf"]}
    q = rng.standard_normal((B, D)).astype(np.float32)
    qterms = rng.integers(0, V, (B, qt)).astype(np.int32)
    gids = np.asarray([i % G for i in range(B)], np.int32)
    preds = [Predicate(tenant=i % 3, min_ts=100) for i in range(G)]
    kw = dict(mode="wsum", w_dense=W_DENSE, w_lex=W_LEX, rrf_c=60.0)

    st_res, st_pg = ExecStats(), ExecStats()
    hot_r = _launch_hybrid(dict(store), lex, q, gids, preds, qterms, k,
                           stats=st_res, shapes=CompiledShapes(), **kw)
    hot_p = _launch_hybrid(dict(store), lex, q, gids, preds, qterms, k,
                           stats=st_pg, shapes=CompiledShapes(),
                           page_rows=256, **kw)
    s_r, i_r = _finish_hot(hot_r)
    s_p, i_p = _finish_hot(hot_p)
    assert (s_r == s_p).all() and (i_r == i_p).all()
    assert st_res.terms_scanned == N * T_LANES
    assert st_pg.terms_scanned == N * T_LANES


# ---------------------------------------------------------------------------
# plan conformance: the planner's paged regime end to end
# ---------------------------------------------------------------------------

def test_paged_plan_execution_bit_identical(rng):
    """compile_plan stamps page_rows past the threshold; execute_plans then
    returns the same bits as the resident plans, counts the paged launches,
    and the EXPLAIN output names the regime."""
    N, D, K = 3000, 16, 8
    store = _arena(rng, N, D)
    q = rng.standard_normal((6, D)).astype(np.float32)
    lps = [LogicalPlan(tenant=t % 3, k=K, q=q[2 * t:2 * t + 2])
           for t in range(3)]
    cfg_res = PlannerConfig()
    cfg_pg = PlannerConfig(paged_min_rows=1, page_rows=512)

    def compiled(cfg):
        return [compile_plan(lp, n_rows=N, hot_window_s=100, now_ts=1000,
                             warm_rows=0, cfg=cfg) for lp in lps]

    plans_res, plans_pg = compiled(cfg_res), compiled(cfg_pg)
    assert plans_res[0].page_rows is None
    assert plans_pg[0].page_rows == 512
    assert "paged arena scan" in plans_pg[0].explain()
    assert "paged regime" in plans_pg[0].engine_reason
    assert plans_res[0].group_key != plans_pg[0].group_key
    assert plans_res[0].fuse_key != plans_pg[0].fuse_key

    st_res, st_pg = ExecStats(), ExecStats()
    s_r, i_r, _ = executor_mod.execute_plans(dict(store), None, plans_res,
                                             stats=st_res)
    s_p, i_p, _ = executor_mod.execute_plans(dict(store), None, plans_pg,
                                             stats=st_pg, planner_cfg=cfg_pg)
    assert (np.asarray(s_r) == np.asarray(s_p)).all()
    assert (np.asarray(i_r) == np.asarray(i_p)).all()
    assert st_res.paged_scans == 0
    assert st_pg.paged_scans >= 1

    # below the threshold the knob stays cold: identical plans, no stamping
    cfg_cold = dataclasses.replace(cfg_pg, paged_min_rows=N + 1)
    assert compiled(cfg_cold)[0].page_rows is None
