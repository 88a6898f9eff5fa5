"""The split-system hybrid baseline: two scans + host-side union rescore.

This is what hybrid retrieval looks like when the lexical engine is a
sidecar (the architecture the paper argues against): the dense engine and
the lexical engine each stream the corpus and return their own top-C list,
and APPLICATION code fuses them. Weighted-sum fusion needs both signals for
every candidate, but each engine only knows its own — so the app issues two
more gather round trips (dense scores of the lexical candidates, BM25 of
the dense candidates) before it can merge. Four device dispatches, a host
merge, and a result that is only exact when every winner landed in one of
the top-C lists.

Two fidelity levels, selected by ``pushdown``:

  * ``pushdown=True`` — a GENEROUS baseline: both sidecars accept the
    lowered predicate and filter inside their scans. No real split stack
    can do this (similarity and lexical services don't carry the tenant /
    ACL / recency columns — that is the paper's point), but it isolates
    the pure two-scans-plus-merge overhead with no filtering confound.
  * ``pushdown=False`` (default, the faithful Stack-A form) — the sidecars
    return UNFILTERED top-C lists; the app fetches metadata, post-filters,
    rescores the union, and RETRIES with a quadrupled fetch when the
    composed predicate under-fills the k-list — the same over-fetch /
    retry ladder as `SplitStackClient.query`, now multiplied by two
    engines. This is where composed keyword+predicate queries (the
    paper's workload) blow the split stack up.

`benchmarks/bench_latency.py` measures both against the one-pass fused
scan (`kernels.arena_scan`, hybrid spec); `tools/check_bench_regression.py
--hybrid-only` gates CI on the fused path staying >= 1.5x faster than the
faithful baseline on the composed query at the 50k-doc point.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.query import NEG_INF, Predicate, predicate_mask, unified_query
from repro.core.store import Store
from repro.kernels.arena_scan.ref import bm25_block, qidf_of, rrf_fuse


@partial(jax.jit, static_argnames=("k",))
def lexical_topk(store: Store, terms, lexnorm, idf, q_terms, pred, k: int):
    """The standalone lexical engine: BM25 over the postings lanes with the
    predicate pushed down, top-k. One of the two scans of the split
    baseline (also the recall reference for "what would BM25 alone do")."""
    qidf = qidf_of(idf, q_terms)
    mask = predicate_mask(store, pred)
    scores = jnp.where(mask[None, :], bm25_block(terms, lexnorm, q_terms,
                                                 qidf), NEG_INF)
    k_eff = min(k, scores.shape[1])
    top_s, top_i = jax.lax.top_k(scores, k_eff)
    return top_s, jnp.where(top_s > NEG_INF, top_i, -1)


@jax.jit
def _gather_dense(emb, q, slots):
    """Rescore round trip 1: dense scores of arbitrary candidate slots."""
    valid = slots >= 0
    rows = emb[jnp.clip(slots, 0)]                       # (B, C, D)
    s = jnp.einsum("bd,bcd->bc", q.astype(jnp.float32),
                   rows.astype(jnp.float32))
    return jnp.where(valid, s, jnp.float32(jnp.finfo(jnp.float32).min))


@jax.jit
def _gather_bm25(terms, lexnorm, idf, q_terms, slots):
    """Rescore round trip 2: BM25 of arbitrary candidate slots."""
    valid = slots >= 0
    t = terms[jnp.clip(slots, 0)]                        # (B, C, T)
    ln = lexnorm[jnp.clip(slots, 0)]
    qidf = qidf_of(idf, q_terms)
    acc = jnp.zeros(slots.shape, jnp.float32)
    for lane in range(t.shape[2]):
        w = jnp.zeros(slots.shape, jnp.float32)
        for j in range(q_terms.shape[1]):
            hit = t[:, :, lane] == q_terms[:, j][:, None]
            w = w + jnp.where(hit, qidf[:, j][:, None], 0.0)
        acc = acc + w * ln[:, :, lane]
    return jnp.where(valid, acc, 0.0)


def _passes_pred(store: Store, slots: np.ndarray, pred: Predicate):
    """App-layer post-filter (the fragile part of the split stack): the
    lowered predicate re-evaluated host-side over fetched metadata."""
    tenant = np.asarray(store["tenant"])[slots]
    ts = np.asarray(store["updated_at"])[slots]
    cat = np.asarray(store["category"])[slots]
    acl = np.asarray(store["acl"])[slots]
    ok = (slots >= 0) & (tenant >= 0) & (ts >= pred.min_ts)
    if pred.tenant != -2:
        ok &= tenant == pred.tenant
    ok &= ((np.uint64(1) << (cat.astype(np.uint64) & np.uint64(31)))
           & np.uint64(pred.cat_mask)) != 0
    ok &= (acl & np.uint32(pred.acl_bits)) != 0
    return ok


def _fuse_union(store, lex_snap, q, q_terms, d_s, d_i, l_s, l_i, k, mode,
                w_dense, w_lex, rrf_c, keep_mask=None):
    """Host-side union fusion over two candidate lists: (wsum) two gather
    rescores fetch each candidate's missing signal, then dedupe + fuse +
    final sort; (rrf) rank fusion straight off the lists."""
    neg = np.float32(np.finfo(np.float32).min)
    if keep_mask is not None:
        d_mask, l_mask = keep_mask
        d_s = np.where(d_mask, d_s, neg)
        d_i = np.where(d_mask, d_i, -1)
        l_s = np.where(l_mask, l_s, neg)
        l_i = np.where(l_mask, l_i, -1)
    if mode == "rrf":
        s, i = rrf_fuse(jnp.asarray(d_s), jnp.asarray(d_i),
                        jnp.asarray(l_s), jnp.asarray(l_i), k, rrf_c)
        return np.asarray(s), np.asarray(i)
    # weighted sum needs BOTH signals on EVERY candidate: two more round
    # trips fetch what each engine couldn't know
    d_of_l = np.asarray(_gather_dense(store["emb"], jnp.asarray(q),
                                      jnp.asarray(l_i)))
    b_of_d = np.asarray(_gather_bm25(lex_snap["terms"], lex_snap["lexnorm"],
                                     lex_snap["idf"],
                                     jnp.asarray(q_terms, jnp.int32),
                                     jnp.asarray(d_i)))
    c = d_i.shape[1]
    cand = np.concatenate([d_i, l_i], axis=1)
    dense_all = np.concatenate([d_s, d_of_l], axis=1)
    lex_all = np.concatenate([b_of_d, np.where(l_i >= 0, l_s, 0.0)], axis=1)
    fused = np.where(cand >= 0,
                     w_dense * dense_all + w_lex * lex_all, neg)
    dup = (l_i[:, None, :] == d_i[:, :, None]) & (l_i[:, None, :] >= 0)
    fused[:, c:][dup.any(axis=1)] = neg          # lex copy of a dense slot
    order = np.argsort(-fused, axis=1, kind="stable")[:, :k]
    s = np.take_along_axis(fused, order, axis=1)
    i = np.take_along_axis(cand, order, axis=1)
    return (np.where(s > neg, s, neg).astype(np.float32),
            np.where(s > neg, i, -1).astype(np.int32))


def two_scan_hybrid(store: Store, lex_snap: dict, q, q_terms,
                    pred: Predicate, k: int, *, mode: str = "wsum",
                    w_dense: float = 1.0, w_lex: float = 1.0,
                    rrf_c: float = 60.0, overfetch: int = 4,
                    max_retries: int = 4, pushdown: bool = False,
                    engine: str = "ref"):
    """The whole split pipeline, timed end to end by the bench. Returns
    (scores (B, k) f32, slots (B, k) i32) numpy.

    ``pushdown=True``: both sidecars filter in-scan (generous baseline —
    isolates the pure two-scan overhead). ``pushdown=False`` (faithful):
    unfiltered top-C from each sidecar, app-layer metadata post-filter,
    union rescore, and the over-fetch retry ladder when the composed
    predicate under-fills — each retry re-streams BOTH engines at 4x the
    fetch, which is exactly how composed queries explode on a split
    stack."""
    n = store["emb"].shape[0]
    q = np.atleast_2d(np.asarray(q, np.float32))
    q_terms = np.asarray(q_terms, np.int32)
    if pushdown:
        c = min(max(overfetch * k, k), n)
        d_s, d_i = unified_query(store, jnp.asarray(q), pred, c,
                                 engine=engine)
        l_s, l_i = lexical_topk(store, lex_snap["terms"],
                                lex_snap["lexnorm"], lex_snap["idf"],
                                jnp.asarray(q_terms, jnp.int32),
                                pred.as_array(), c)
        return _fuse_union(store, lex_snap, q, q_terms,
                           np.asarray(d_s), np.asarray(d_i),
                           np.asarray(l_s), np.asarray(l_i), k, mode,
                           w_dense, w_lex, rrf_c)
    # faithful split: similarity and lexical services know nothing about
    # tenants / ACLs / recency — scan unfiltered, post-filter app-side,
    # retry with a quadrupled fetch on under-fill
    open_pred = Predicate()
    fetch = min(max(overfetch * k, k), n)
    while True:
        d_s, d_i = unified_query(store, jnp.asarray(q), open_pred, fetch,
                                 engine=engine)
        l_s, l_i = lexical_topk(store, lex_snap["terms"],
                                lex_snap["lexnorm"], lex_snap["idf"],
                                jnp.asarray(q_terms, jnp.int32),
                                open_pred.as_array(), fetch)
        d_s, d_i, l_s, l_i = jax.device_get((d_s, d_i, l_s, l_i))
        d_ok = _passes_pred(store, np.maximum(d_i, 0), pred) & (d_i >= 0)
        l_ok = _passes_pred(store, np.maximum(l_i, 0), pred) & (l_i >= 0)
        # under-filled when the union of qualifying candidates cannot fill
        # k for some row (conservative per-list check, like Stack A's)
        filled = ((d_ok.sum(axis=1) >= k) | (l_ok.sum(axis=1) >= k)
                  | (fetch >= n))
        if filled.all() or fetch >= n or max_retries == 0:
            return _fuse_union(store, lex_snap, q, q_terms, d_s, d_i,
                               l_s, l_i, k, mode, w_dense, w_lex, rrf_c,
                               keep_mask=(d_ok, l_ok))
        fetch = min(fetch * 4, n)
        max_retries -= 1
