"""Pallas TPU kernel: the unified arena scan, in two residency regimes.

**Resident** (arena streams through BlockSpec grid pipelining):

  grid = (B_blocks, N_blocks)              # N innermost -> sequential scan
  per step:
    VMEM tiles:  q (BLK_B, D), emb (BLK_N, D), meta (M, BLK_N) int32,
                 [terms (T, BLK_N) int32, lexnorm (T, BLK_N) f32,
                  qterms (BLK_B, QT) int32, qidf (BLK_B, QT) f32],
                 gids (BLK_B, 1), preds (G, 4) int32 (replicated)
    stages:      score (MXU dot [+ VPU BM25]) + mask (predicate groups via
                 one-hot matmul [+ slot-lane membership])
    scratch:     running top-k per signal list (ORDER BY .. LIMIT k)
    gate:        a list merges the tile only where it holds a row above
                 some query row's k-th entry (see below)

  Pallas pipelines the tile copies against compute automatically — the
  right regime while the working set of in-flight tiles fits VMEM.

**Paged** (HBM-resident arena, explicit double-buffered DMA):

  grid = (B_blocks,)                       # the page loop lives IN the body
  the arena streams (emb, meta [, terms, lexnorm]) stay in ANY memory
  (HBM); each stream owns a double VMEM scratch buffer ((2, PAGE, D) for
  emb, (2, width, PAGE) for the lane-major narrow streams, so every page
  DMA slices the arena along an axis tiled in multiples of 128) and a
  2-slot DMA semaphore. The page loop overlaps copy with compute:

      start(page 0 -> slot 0)
      for p in pages:                      #  DMA      |  compute
          start(page p+1 -> slot p+1 & 1)  #  p+1 in   |
          wait(page p  -> slot p & 1)      #  flight   |  score+mask+merge
          merge(tile_step(slot p & 1))     #           |  page p
      flush running lists

  This makes arenas LARGER than VMEM a first-class regime instead of a
  cliff: the scan runs at HBM stream speed with one page of latency
  hidden, and the page size is a planner knob (`PhysicalPlan.page_rows`),
  not a compile-time constant.

**The merge gate.** Past the first tiles a tile rarely holds a row that
beats a query row's k-th best, and `stages.merge_topk` (k selection
rounds over the list and the tile) is most of a pass's compute. So each
running list merges a tile only where some row's best tile score is
STRICTLY above that row's k-th entry, ``s[:, k-1]`` (the list is kept in
descending order). Skipping is exact: a tile score equal to the k-th
entry loses its tie to the list, which comes first in the merge; masked
rows are ``NEG_INF`` and beat nothing, while a list not yet full holds
``NEG_INF`` entries that any kept row beats; padding query rows only
make a merge more likely. rrf's two lists gate on their own signals.
Every merge counts into one SMEM int32 per (query block, list), the
kernel's last output: the share of tiles merged is what the gate saves.

Bit-identity across regimes is structural: both run the same
`stages.tile_mask` + `stages.tile_signals` + `stages.merge_topk` per tile,
and paged mode's merge schedule at page size P equals resident mode's (and
the jnp streaming ref's) at blk_n = P — so one conformance matrix covers
every (engine, regime, page size) cell (tests/test_arena_scan_conformance).

CPU CI executes both regimes in interpret mode; tests/test_tpu_compile.py
compiles both for a described TPU v5e at D=768, and chip_smoke.py runs the
resident regime compiled on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.arena_scan.stages import (B_LANES, NEG_INF, ScanSpec,
                                             merge_topk, tile_mask,
                                             tile_signals)


#: Scoped VMEM of the paged regime (a v5e core has 128 MiB of VMEM).
PAGED_VMEM_BYTES = 32 * 2**20


def _tile_step(spec: ScanSpec, k: int, scratch, merged, q, e, meta, gids,
               preds, base, lex):
    """One tile through the shared stages: mask -> score -> merge into the
    running lists, each list only where the tile can enter it (the merge
    gate, module docstring). ``merged`` is the query block's (SMEM ref,
    offset) pair: one int32 per list counts its merges. ``base`` is the
    tile's arena offset (index source for positional engines; slot-lane
    engines index from meta[4])."""
    row_keep = tile_mask(spec, meta, preds, gids, onehot=True)
    signals = tile_signals(spec, q, e, row_keep, lex)
    if spec.slot_lane:
        idx = jnp.broadcast_to(meta[4:5, :], signals[0].shape)
    else:
        idx = base + jax.lax.broadcasted_iota(jnp.int32, signals[0].shape, 1)
    cnt_ref, at = merged
    for j, ((s_ref, i_ref), sig) in enumerate(zip(scratch, signals)):
        best = jnp.max(sig, axis=1, keepdims=True)                 # (B, 1)
        enters = jnp.max(jnp.where(best > s_ref[:, k - 1:k], 1, 0))

        @pl.when(enters > 0)
        def _merge():
            new_s, new_i = merge_topk(s_ref[...], i_ref[...], sig, idx, k)
            s_ref[...] = new_s
            i_ref[...] = new_i
            cnt_ref[at + j] += 1


def _init_lists(scratch, merged):
    cnt_ref, at = merged
    for j, (s_ref, i_ref) in enumerate(scratch):
        s_ref[...] = jnp.full(s_ref.shape, NEG_INF, jnp.float32)
        i_ref[...] = jnp.full(i_ref.shape, -1, jnp.int32)
        cnt_ref[at + j] = 0


def _flush_lists(outs, scratch):
    for (os_ref, oi_ref), (s_ref, i_ref) in zip(outs, scratch):
        os_ref[...] = s_ref[...]
        oi_ref[...] = jnp.where(s_ref[...] > NEG_INF, i_ref[...], -1)


def _split_refs(spec: ScanSpec, refs):
    """Outputs ((s, i) pairs, then the merge counts), then the scratch
    lists, (s, i) pairs. The counts come as (ref, this query block's
    offset): `pl.program_id` is read here, at the kernel's top level."""
    n = spec.n_lists
    outs = tuple((refs[2 * j], refs[2 * j + 1]) for j in range(n))
    merged = (refs[2 * n], pl.program_id(0) * n)
    scratch = tuple((refs[2 * n + 1 + 2 * j], refs[2 * n + 2 + 2 * j])
                    for j in range(n))
    return outs, merged, scratch, refs[4 * n + 1:]


def _resident_kernel(gid_ref, pred_ref, q_ref, emb_ref, meta_ref, *refs,
                     spec: ScanSpec, k: int, blk_n: int):
    if spec.has_lex:
        terms_ref, ln_ref, qterms_ref, qidf_ref, *refs = refs
        lex = (terms_ref[...], ln_ref[...], qterms_ref[...], qidf_ref[...])
    else:
        lex = None
    outs, merged, scratch, rest = _split_refs(spec, refs)
    assert not rest
    bn = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(bn == 0)
    def _init():
        _init_lists(scratch, merged)

    _tile_step(spec, k, scratch, merged, q_ref[...], emb_ref[...],
               meta_ref[...], gid_ref[...], pred_ref[...], bn * blk_n, lex)

    @pl.when(bn == n_blocks - 1)
    def _finish():
        _flush_lists(outs, scratch)


def _paged_kernel(gid_ref, pred_ref, q_ref, *refs, spec: ScanSpec, k: int,
                  page: int, n_pages: int):
    """The page loop with explicit double-buffered DMA (module docstring).
    Arg layout after the VMEM-resident smalls: [qterms, qidf,] HBM streams
    (emb, meta [, terms, lexnorm]), outputs, running-list scratch, then per
    stream a double page buffer + a 2-slot DMA semaphore. emb pages slice
    rows; the lane-major narrow streams slice columns."""
    if spec.has_lex:
        qterms_ref, qidf_ref, *refs = refs
        qlex = (qterms_ref[...], qidf_ref[...])
    n_streams = 4 if spec.has_lex else 2
    hbm = refs[:n_streams]
    outs, merged, scratch, rest = _split_refs(spec, refs[n_streams:])
    bufs = rest[:n_streams]
    sems = rest[n_streams:]
    assert len(sems) == n_streams

    def copies(slot, p):
        rows = pl.ds(p * page, page)
        srcs = [hbm[0].at[rows]] + [h.at[:, rows] for h in hbm[1:]]
        return [pltpu.make_async_copy(src, b.at[slot], s.at[slot])
                for src, b, s in zip(srcs, bufs, sems)]

    _init_lists(scratch, merged)
    q = q_ref[...]
    gids = gid_ref[...]
    preds = pred_ref[...]
    for c in copies(0, 0):
        c.start()

    def body(p, _):
        slot = jax.lax.rem(p, 2)
        nxt = jax.lax.rem(p + 1, 2)

        @pl.when(p + 1 < n_pages)
        def _prefetch():
            for c in copies(nxt, p + 1):
                c.start()

        for c in copies(slot, p):
            c.wait()
        e = bufs[0][slot]
        meta = bufs[1][slot]
        lex = ((bufs[2][slot], bufs[3][slot]) + qlex if spec.has_lex
               else None)
        _tile_step(spec, k, scratch, merged, q, e, meta, gids, preds,
                   p * page, lex)
        return 0

    jax.lax.fori_loop(0, n_pages, body, 0)
    _flush_lists(outs, scratch)


def arena_scan_pallas(q: jax.Array, emb: jax.Array, meta: jax.Array,
                      gids: jax.Array, preds: jax.Array, k: int, *,
                      spec: ScanSpec = ScanSpec(),
                      lex: tuple | None = None,
                      blk_b: int = B_LANES, blk_n: int = 512,
                      page_rows: int | None = None,
                      interpret: bool = False):
    """The unified scan. q: (B, D); emb: (N, D); meta: (M, N) int32,
    lane-major, with M = `spec.meta_width`; gids: (B, 1) int32 group id per
    query row; preds: (G, 4) int32 stacked lowered predicates; ``lex``
    (when `spec.has_lex`) is (terms (T, N) int32, lexnorm (T, N) f32,
    qterms (B, QT) int32, qidf (B, QT) f32 — fusion weights pre-folded).

    B % blk_b == 0, D % 128 == 0, and N % blk_n == 0 (resident) or
    N % page_rows == 0 (paged), with blk_n / page_rows multiples of 128 —
    `ops.arena_scan` pads. Returns
    `spec.n_lists` (scores (B, k) f32, indices (B, k) i32) pairs,
    flattened, then the merge counts: (B // blk_b * n_lists,) int32, the
    tiles each (query block, list) merged (module docstring). ``page_rows`` selects the paged regime; its merge schedule
    (and thus its bits) equals resident mode at blk_n = page_rows."""
    B, D = q.shape
    N = emb.shape[0]
    G = preds.shape[0]
    M = spec.meta_width
    assert B % blk_b == 0, (B, blk_b)
    assert meta.shape[0] == M, (meta.shape, M)
    assert gids.shape == (B, 1), gids.shape
    n_lists = spec.n_lists
    out_shape = ((jax.ShapeDtypeStruct((B, k), jnp.float32),
                  jax.ShapeDtypeStruct((B, k), jnp.int32)) * n_lists
                 + (jax.ShapeDtypeStruct((B // blk_b * n_lists,), jnp.int32),))
    merged_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    list_scratch = (pltpu.VMEM((blk_b, k), jnp.float32),
                    pltpu.VMEM((blk_b, k), jnp.int32)) * n_lists

    if page_rows is None:
        assert N % blk_n == 0, (N, blk_n)
        grid = (B // blk_b, N // blk_n)
        in_specs = [
            pl.BlockSpec((blk_b, 1), lambda b, n: (b, 0)),   # gids
            pl.BlockSpec((G, 4), lambda b, n: (0, 0)),       # preds
            pl.BlockSpec((blk_b, D), lambda b, n: (b, 0)),   # q
            pl.BlockSpec((blk_n, D), lambda b, n: (n, 0)),   # emb
            pl.BlockSpec((M, blk_n), lambda b, n: (0, n)),   # meta
        ]
        inputs = [gids, preds, q, emb, meta]
        if spec.has_lex:
            terms, lexnorm, qterms, qidf = lex
            T, QT = terms.shape[0], qterms.shape[1]
            in_specs += [
                pl.BlockSpec((T, blk_n), lambda b, n: (0, n)),   # terms
                pl.BlockSpec((T, blk_n), lambda b, n: (0, n)),   # lexnorm
                pl.BlockSpec((blk_b, QT), lambda b, n: (b, 0)),  # qterms
                pl.BlockSpec((blk_b, QT), lambda b, n: (b, 0)),  # qidf
            ]
            inputs += [terms, lexnorm, qterms, qidf]
        kernel = functools.partial(_resident_kernel, spec=spec, k=k,
                                   blk_n=blk_n)
        out_spec = ((pl.BlockSpec((blk_b, k), lambda b, n: (b, 0)),) * 2
                    * n_lists + (merged_spec,))
        scratch = list(list_scratch)
        params = None
    else:
        page = page_rows
        assert N % page == 0, (N, page)
        grid = (B // blk_b,)
        in_specs = [
            pl.BlockSpec((blk_b, 1), lambda b: (b, 0)),      # gids
            pl.BlockSpec((G, 4), lambda b: (0, 0)),          # preds
            pl.BlockSpec((blk_b, D), lambda b: (b, 0)),      # q
        ]
        inputs = [gids, preds, q]
        stream_shapes = [((page, D), jnp.float32), ((M, page), jnp.int32)]
        if spec.has_lex:
            terms, lexnorm, qterms, qidf = lex
            T, QT = terms.shape[0], qterms.shape[1]
            in_specs += [
                pl.BlockSpec((blk_b, QT), lambda b: (b, 0)),  # qterms
                pl.BlockSpec((blk_b, QT), lambda b: (b, 0)),  # qidf
            ]
            inputs += [qterms, qidf]
            stream_shapes += [((T, page), jnp.int32), ((T, page), jnp.float32)]
        # the arena streams stay HBM-resident; the body DMAs pages itself
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(stream_shapes)
        inputs += ([emb, meta, terms, lexnorm] if spec.has_lex
                   else [emb, meta])
        kernel = functools.partial(_paged_kernel, spec=spec, k=k, page=page,
                                   n_pages=N // page)
        out_spec = ((pl.BlockSpec((blk_b, k), lambda b: (b, 0)),) * 2
                    * n_lists + (merged_spec,))
        scratch = list(list_scratch)
        scratch += [pltpu.VMEM((2,) + shape, dt) for shape, dt in stream_shapes]
        scratch += [pltpu.SemaphoreType.DMA((2,))] * len(stream_shapes)
        # the emb double buffer alone is 12.6 MB at the planner's 2048-row
        # page of 768 f32, and the merge gate's reduction and branch take
        # Mosaic 2-5 MB of stack more: over the default 16 MiB scope
        params = pltpu.CompilerParams(vmem_limit_bytes=PAGED_VMEM_BYTES)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0, grid=grid, in_specs=in_specs,
        out_specs=list(out_spec), scratch_shapes=scratch)
    fn = pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                        compiler_params=params, interpret=interpret)
    return fn(*inputs)
