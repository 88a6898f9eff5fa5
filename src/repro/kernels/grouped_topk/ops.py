"""jit'd public wrapper for the grouped_topk kernel.

Handles metadata packing, padding to tile multiples, and engine dispatch
(Pallas on TPU, jnp ref elsewhere; tests pass ``use_kernel=True,
interpret=True`` to execute the kernel body on CPU).

Padding invariants (shared with every arena-scan family — see
`repro.kernels.arena_scan.ops`):
  * arena rows pad to the N-block (or page) multiple as DEAD rows
    (tenant = -1) for BOTH engines, so kernel and ref run on identical
    arrays and bit-identity is testable;
  * query rows pad to the B-block multiple with group id 0 — retrieval is
    row-parallel, so padding rows cannot perturb real rows, and they are
    sliced off before returning;
  * the caller may pad ``preds`` with blocker rows (tenant = -3, which no
    live row can match) to bucket G for compiled-shape reuse — a blocker
    group masks everything, and no real row carries its group id.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.ops import (BLK_SCAN,  # noqa: F401
                                          _META_CACHE, _pack_meta,
                                          _packed_meta, _pad_axis0,
                                          default_blk_b, default_blk_n,
                                          default_interpret,
                                          default_use_kernel, pad_d128,
                                          pad_dead_rows)
from repro.kernels.arena_scan.stages import ScanSpec
from repro.kernels.grouped_topk.grouped_topk import grouped_topk_pallas
from repro.kernels.grouped_topk.ref import NEG_INF, grouped_topk_scan_ref


@partial(jax.jit, static_argnames=("k", "use_kernel", "blk_b", "blk_n",
                                   "page_rows", "interpret"))
def _run(q, emb, meta, gids, preds, k, use_kernel, blk_b, blk_n, page_rows,
         interpret):
    # pad N to the block (or page) multiple with dead rows (tenant = -1)
    # for BOTH engines, so kernel and ref stream identically-shaped arenas
    emb, meta = pad_dead_rows(emb, meta, page_rows or blk_n)
    if not use_kernel:
        # the scan tile IS the page: blk_n = page_rows in the paged regime
        return grouped_topk_scan_ref(q, emb, meta, gids, preds, k,
                                     page_rows or blk_n)
    B = q.shape[0]
    q, emb = pad_d128(q, emb)
    q = _pad_axis0(q, blk_b, 0)
    gids = _pad_axis0(gids.reshape(-1, 1), blk_b, 0)
    s, i = grouped_topk_pallas(q, emb, meta, gids, preds, k,
                               blk_b=blk_b, blk_n=blk_n, page_rows=page_rows,
                               interpret=interpret)
    return s[:B], i[:B]


def grouped_topk(q, emb, tenant, updated_at, category, acl, gids, preds,
                 k: int, *, use_kernel: bool | None = None,
                 blk_b: int | None = None, blk_n: int | None = None,
                 page_rows: int | None = None,
                 interpret: bool | None = None):
    """Fused multi-predicate grouped top-k over one arena scan.

    q: (B, D) stacked query rows for EVERY predicate group in the batch;
    emb/tenant/updated_at/category/acl: the arena columns; gids: (B,) int32
    group id per query row (values in [0, G)); preds: (G, 4) int32 stacked
    `Predicate.as_array()` rows; k: LIMIT.
    Returns (scores (B, k) f32, slots (B, k) i32, -1 past the fill).

    ``use_kernel=None`` picks the Pallas kernel on a TPU backend and the jnp
    streaming scan elsewhere; tests pass ``use_kernel=True, interpret=True``
    to execute the kernel body on CPU. ``blk_b=None`` takes
    `default_blk_b`: the kernel holds the whole batch in one query-row
    block, so the arena streams once. ``blk_n=None`` picks the engine's
    default tile (512 VMEM rows for the kernel; `BLK_SCAN` for the jnp
    scan, clamped to the arena so small stores stay single-tile).
    ``page_rows`` selects the paged regime: the Pallas kernel switches to
    HBM-resident streams with double-buffered DMA, the jnp scan tiles at
    the page size — bits are unchanged either way (arena_scan contract).
    """
    use_kernel = default_use_kernel(use_kernel)
    interpret = default_interpret(interpret)
    if blk_b is None:
        blk_b = default_blk_b(q.shape[0], ScanSpec())
    if blk_n is None:
        blk_n = default_blk_n(emb.shape[0], use_kernel)
    n = emb.shape[0]
    if k > n:   # LIMIT larger than the arena: SQL semantics, padded to k
        s, i = grouped_topk(q, emb, tenant, updated_at, category, acl, gids,
                            preds, n, use_kernel=use_kernel, blk_b=blk_b,
                            blk_n=blk_n, page_rows=page_rows,
                            interpret=interpret)
        pad = ((0, 0), (0, k - n))
        return (jnp.pad(s, pad, constant_values=NEG_INF),
                jnp.pad(i, pad, constant_values=-1))
    meta = _packed_meta(tenant, updated_at, category, acl)
    return _run(jnp.asarray(q), emb, meta, jnp.asarray(gids, jnp.int32),
                jnp.asarray(preds, jnp.int32), k, use_kernel, blk_b, blk_n,
                page_rows, interpret)
