"""jit'd public wrapper for the hybrid_score kernel.

Handles metadata packing, padding to tile multiples, query-side idf
gathering, engine dispatch (Pallas on TPU, jnp streaming scan elsewhere;
tests pass ``use_kernel=True, interpret=True`` to execute the kernel body
on CPU), and the RRF rank fusion of the kernel's per-signal lists.

Padding invariants (shared with every arena-scan family — see
`repro.kernels.arena_scan.ops`):
  * arena rows pad to the N-block (or page) multiple as DEAD rows
    (tenant = -1, term lanes empty, lexnorm 0) for BOTH engines, so kernel
    and refs run on identical arrays and bit-identity is testable;
  * query rows pad to the B-block multiple with group id 0 and no query
    terms — retrieval is row-parallel, so padding rows cannot perturb real
    rows, and they are sliced off before returning;
  * the caller may pad ``preds`` with blocker rows (tenant = -3) to bucket
    G, and ``qterms`` columns with -1 to bucket QT — a -1 query term can
    only "match" an empty doc lane and its gathered idf is forced to 0, so
    padded term lanes contribute exactly 0.0 to every score.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.ops import (_packed_lanes, _packed_meta,
                                          _pad_axis0, default_blk_b,
                                          default_blk_n, default_interpret,
                                          default_use_kernel, pad_d128,
                                          pad_dead_rows)
from repro.kernels.hybrid_score.hybrid_score import (hybrid_score_pallas,
                                                     hybrid_spec)
from repro.kernels.hybrid_score.ref import (NEG_INF, hybrid_score_scan_ref,
                                            qidf_of, rrf_fuse)


@partial(jax.jit, static_argnames=("k", "mode", "w_dense", "w_lex", "rrf_c",
                                   "lists", "use_kernel", "blk_b", "blk_n",
                                   "page_rows", "interpret"))
def _run(q, emb, meta, terms, lexnorm, idf, gids, preds, qterms, k, mode,
         w_dense, w_lex, rrf_c, lists, use_kernel, blk_b, blk_n, page_rows,
         interpret):
    qidf = qidf_of(idf, qterms)
    # pad N to the block (or page) multiple with dead rows for BOTH engines
    emb, meta, terms, lexnorm = pad_dead_rows(emb, meta, page_rows or blk_n,
                                              terms, lexnorm)
    if not use_kernel:
        # the scan tile IS the page: blk_n = page_rows in the paged regime
        return hybrid_score_scan_ref(q, emb, meta, terms, lexnorm, gids,
                                     preds, qterms, qidf, k,
                                     page_rows or blk_n,
                                     mode=mode, w_dense=w_dense, w_lex=w_lex,
                                     rrf_c=rrf_c, lists=lists)
    B = q.shape[0]
    q, emb = pad_d128(q, emb)
    q = _pad_axis0(q, blk_b, 0)
    gids = _pad_axis0(gids.reshape(-1, 1), blk_b, 0)
    qterms = _pad_axis0(qterms, blk_b, -1)
    qidf = _pad_axis0(qidf, blk_b, 0)
    out = hybrid_score_pallas(q, emb, meta, terms, lexnorm, gids, preds,
                              qterms, qidf, k, mode=mode, w_dense=w_dense,
                              w_lex=w_lex, blk_b=blk_b, blk_n=blk_n,
                              page_rows=page_rows, interpret=interpret)
    if mode == "wsum":
        s, i = out
        return s[:B], i[:B]
    d_s, d_i, l_s, l_i = (a[:B] for a in out)
    if lists:
        return d_s, d_i, l_s, l_i
    return rrf_fuse(d_s, d_i, l_s, l_i, k, rrf_c)


def hybrid_score(q, emb, tenant, updated_at, category, acl, terms, lexnorm,
                 idf, gids, preds, qterms, k: int, *, mode: str = "wsum",
                 w_dense: float = 1.0, w_lex: float = 1.0,
                 rrf_c: float = 60.0, lists: bool = False,
                 use_kernel: bool | None = None, blk_b: int | None = None,
                 blk_n: int | None = None, page_rows: int | None = None,
                 interpret: bool | None = None):
    """Fused hybrid dense+BM25 grouped top-k over ONE arena scan.

    q: (B, D) stacked query rows for every predicate group in the batch;
    emb/tenant/updated_at/category/acl: the vector-arena columns;
    terms/lexnorm: the postings-arena lanes ((N, T) ids + precomputed
    per-lane BM25 weight, `LexicalArena.snapshot()`); idf: (V,) f32 table;
    gids: (B,) int32 group id per row; preds: (G, 4) int32 stacked
    `Predicate.as_array()` rows; qterms: (B, QT) int32 per-row query term
    ids (-1 padding); k: LIMIT.

    ``mode="wsum"`` ranks on w_dense*dense + w_lex*bm25 (weights folded
    into the inputs — see hybrid_score.py); ``mode="rrf"`` retrieves both
    per-signal k-lists in the same pass and rank-fuses them
    (1/(rrf_c + rank), deduplicated union). ``lists=True`` (rrf only)
    skips the fusion and returns (d_s, d_i, l_s, l_i) — the tiered
    executor merges per signal across tiers first.

    Returns (scores (B, k) f32, slots (B, k) i32, -1 past the fill).
    ``use_kernel=None`` picks the Pallas kernel on a TPU backend and the
    jnp streaming scan elsewhere; tests pass ``use_kernel=True,
    interpret=True`` to execute the kernel body on CPU. ``blk_b=None``
    takes `default_blk_b` (8-row blocks: the BM25 stage is per query row).
    ``page_rows`` selects the paged regime: the Pallas kernel switches to
    HBM-resident streams with double-buffered DMA, the jnp scan tiles at
    the page size — bits are unchanged either way (arena_scan contract).
    """
    if lists and mode != "rrf":
        raise ValueError("lists=True is only meaningful for mode='rrf'")
    if mode not in ("wsum", "rrf"):
        raise ValueError(f"unknown fusion mode {mode!r}")
    use_kernel = default_use_kernel(use_kernel)
    interpret = default_interpret(interpret)
    if blk_b is None:
        blk_b = default_blk_b(q.shape[0], hybrid_spec(mode))
    if blk_n is None:
        blk_n = default_blk_n(emb.shape[0], use_kernel)
    n = emb.shape[0]
    if k > n:   # LIMIT larger than the arena: SQL semantics, padded to k
        out = hybrid_score(q, emb, tenant, updated_at, category, acl, terms,
                           lexnorm, idf, gids, preds, qterms, n, mode=mode,
                           w_dense=w_dense, w_lex=w_lex, rrf_c=rrf_c,
                           lists=lists, use_kernel=use_kernel, blk_b=blk_b,
                           blk_n=blk_n, page_rows=page_rows,
                           interpret=interpret)
        pad = ((0, 0), (0, k - n))
        return tuple(jnp.pad(a, pad, constant_values=NEG_INF) if j % 2 == 0
                     else jnp.pad(a, pad, constant_values=-1)
                     for j, a in enumerate(out))
    meta = _packed_meta(tenant, updated_at, category, acl)
    terms_t, lexnorm_t = _packed_lanes(terms, lexnorm)
    return _run(jnp.asarray(q), emb, meta, terms_t, lexnorm_t,
                jnp.asarray(idf, jnp.float32),
                jnp.asarray(gids, jnp.int32), jnp.asarray(preds, jnp.int32),
                jnp.asarray(qterms, jnp.int32), k, mode, float(w_dense),
                float(w_lex), float(rrf_c), lists, use_kernel, blk_b, blk_n,
                page_rows, interpret)
