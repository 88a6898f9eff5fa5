"""Pure-jnp reference for the hybrid_score kernel.

Contract shared with the Pallas kernel (hybrid_score.py): ONE pass over the
arena computes BOTH retrieval signals for every query row —

  dense  = (w_dense * q) . emb^T           (cosine / dot similarity)
  bm25   = sum over the row's T postings lanes of
           w_lex * idf(term) * tf*(k1+1)/(tf + k1*lennorm)   (masked gather)

— applies the row's lowered predicate mask (grouped, exactly as
grouped_topk: a row failing group g's predicate is -inf in every g-row's
lane BEFORE any ranking and can never surface no matter how high its BM25
score), and maintains a running top-k on the FUSED score:

  * ``wsum``: fused = dense + bm25 with the fusion weights FOLDED into the
              inputs (q and qidf) — arena-scan pinning rule 1: a weighted
              combine at the output is an FMA-contractible mul+add whose
              rounding depends on the surrounding fusion; the bare add is
              not. One running k-list.
  * ``rrf``:  two running k-lists (dense, bm25), fused by reciprocal-rank
              over the retrieved lists (`rrf_fuse`) after the scan — rank
              fusion needs ranks, which only exist once the lists do, so
              this is the one-pass form every production RRF uses. Weights
              are unused (ranks are scale-free).

BIT-IDENTITY between kernel, dense oracle, and streaming scan is by
construction: all three are the arena-scan framework's engines running the
same stage functions (arena_scan/stages.py) with identical weight folding.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.ref import arena_scan_ref, arena_scan_scan_ref
from repro.kernels.arena_scan.stages import ScanSpec, bm25_scores

NEG_INF = jnp.float32(jnp.finfo(jnp.float32).min)


def qidf_of(idf: jax.Array, qterms: jax.Array) -> jax.Array:
    """Query-side idf gather: (B, QT) term ids against the snapshot's (V,)
    idf table. Padding terms (-1) gather weight 0 — the invariant that
    makes padded term lanes inert in every scorer (kernel, refs, warm
    pushdown, split baseline), so it lives in exactly one place."""
    return jnp.where(qterms >= 0,
                     idf[jnp.clip(qterms, 0, idf.shape[0] - 1)], 0.0
                     ).astype(jnp.float32)


def bm25_block(terms: jax.Array, lexnorm: jax.Array, qterms: jax.Array,
               qidf: jax.Array) -> jax.Array:
    """Masked-gather BM25 over one block of (N, T) postings lanes — the
    arena-scan framework's lexical score stage on the row-major layout the
    split-stack scorers hold (see `arena_scan.stages.bm25_scores` for the
    fixed accumulation order and the select-guarded lane product that pin
    its bits across fusion contexts). Returns (B, N) f32."""
    return bm25_scores(terms.T, lexnorm.T, qterms, qidf)


def rrf_fuse(ds: jax.Array, di: jax.Array, ls: jax.Array, li: jax.Array,
             k: int, c: float):
    """Reciprocal-rank fusion of two per-signal k-lists (the standard
    retrieved-lists form): candidate score = sum over lists containing it of
    1/(c + rank). A candidate in both lists is represented by its dense-list
    copy (the lex copy is masked out), so the union is deduplicated exactly.
    Returns (scores (B, k) f32, slots (B, k) i32, -1 past the fill).

    Ties (e.g. rank r in dense only vs rank r in lex only) break toward the
    dense list, then toward the better rank — `lax.top_k` lower-index-first
    over the [dense | lex] concatenation, deterministically.
    """
    kd, kl = di.shape[1], li.shape[1]
    rd = 1.0 / (c + jnp.arange(1, kd + 1, dtype=jnp.float32))
    rl = 1.0 / (c + jnp.arange(1, kl + 1, dtype=jnp.float32))
    d_valid = di >= 0
    l_valid = li >= 0
    cross = ((di[:, :, None] == li[:, None, :])
             & d_valid[:, :, None] & l_valid[:, None, :])        # (B, kd, kl)
    d_score = (jnp.where(d_valid, rd[None, :], NEG_INF)
               + jnp.sum(jnp.where(cross, rl[None, None, :], 0.0), axis=2))
    # a lex candidate also in the dense list already carries both ranks on
    # its dense copy — mask the lex copy out so the union stays deduplicated
    in_dense = cross.any(axis=1)                                 # (B, kl)
    l_score = jnp.where(l_valid & ~in_dense, rl[None, :], NEG_INF)
    all_s = jnp.concatenate([d_score, l_score], axis=1)
    all_i = jnp.concatenate([di, li], axis=1)
    k_eff = min(k, all_s.shape[1])
    top_s, sel = jax.lax.top_k(all_s, k_eff)
    top_i = jnp.take_along_axis(all_i, sel, axis=1)
    if k_eff < k:
        pad = ((0, 0), (0, k - k_eff))
        top_s = jnp.pad(top_s, pad, constant_values=NEG_INF)
        top_i = jnp.pad(top_i, pad, constant_values=-1)
    return top_s, jnp.where(top_s > NEG_INF, top_i, -1)


def _fold(q, qidf, mode, w_dense, w_lex):
    """Identical weight folding in every engine (pinning rule 1): wsum
    scales the inputs once, elementwise — the same bits no matter which
    engine performs the multiply. RRF leaves inputs untouched (rank fusion
    is scale-free and its lists carry RAW signal scores)."""
    if mode == "wsum":
        return q * jnp.float32(w_dense), qidf * jnp.float32(w_lex)
    return q, qidf


@partial(jax.jit, static_argnames=("k", "mode", "w_dense", "w_lex", "rrf_c"))
def hybrid_score_ref(q, emb, meta, terms, lexnorm, gids, preds, qterms, qidf,
                     k: int, mode: str = "wsum", w_dense: float = 1.0,
                     w_lex: float = 1.0, rrf_c: float = 60.0):
    """Dense oracle. q: (B, D); emb: (N, D); meta: (4, N) int32 lane-major;
    terms / lexnorm: (T, N) lane-major; gids: (B,) int32; preds: (G, 4) int32; qterms: (B, QT)
    int32; qidf: (B, QT) f32. Returns (scores (B, k) f32, slots (B, k) i32)
    for ``wsum`` and the fused RRF lists for ``rrf``."""
    q, qidf = _fold(q, qidf, mode, w_dense, w_lex)
    spec = ScanSpec(score="fused" if mode == "wsum" else "both")
    out = arena_scan_ref(q, emb, meta, gids, preds, k, spec=spec,
                         lex=(terms, lexnorm, qterms, qidf))
    if mode == "wsum":
        return out
    return rrf_fuse(*out, k, rrf_c)


@partial(jax.jit, static_argnames=("k", "mode", "w_dense", "w_lex", "rrf_c",
                                   "blk_n", "lists"))
def hybrid_score_scan_ref(q, emb, meta, terms, lexnorm, gids, preds, qterms,
                          qidf, k: int, blk_n: int, mode: str = "wsum",
                          w_dense: float = 1.0, w_lex: float = 1.0,
                          rrf_c: float = 60.0, lists: bool = False):
    """Streaming jnp implementation — the kernel's schedule without Pallas:
    scan the arena in (blk_n,) tiles, compute dense + masked-gather BM25 +
    predicate mask per tile, keep a LOCAL top-k per running list, one final
    merge over the (tiles*k)-wide candidates. Never materializes (B, N) —
    on the CPU rig this is the production one-pass hybrid engine.

    ``lists=True`` (rrf only) returns the two per-signal k-lists unfused —
    the tiered executor merges them with the warm tier's lists per signal
    before rank fusion. N % blk_n == 0 (ops.py pads).
    """
    q, qidf = _fold(q, qidf, mode, w_dense, w_lex)
    spec = ScanSpec(score="fused" if mode == "wsum" else "both")
    out = arena_scan_scan_ref(q, emb, meta, gids, preds, k, blk_n, spec=spec,
                              lex=(terms, lexnorm, qterms, qidf))
    if mode == "wsum":
        return out
    if lists:
        return out
    return rrf_fuse(*out, k, rrf_c)
