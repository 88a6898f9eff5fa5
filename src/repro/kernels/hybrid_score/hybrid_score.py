"""Pallas TPU kernel: fused hybrid dense+BM25 grouped top-k — one arena
pass computes BOTH retrieval signals, applies the lowered predicate mask,
and keeps the running top-k on the fused score.

The split-system alternative scans twice (dense engine, lexical engine) and
merges app-side — two HBM streams over the corpus plus rescore round trips
for whichever signal each candidate list is missing. Retrieval at this
scale is memory-bandwidth-bound, so this kernel streams each arena tile
ONCE and computes everything in the same VMEM residency:

  MXU:         dense    = (w_dense * q) @ emb^T
  VPU:         bm25     = masked-gather over postings lanes with the
                          lex weight folded into qidf
               keep_g   = ALL G predicate masks, one broadcast pass
  MXU:         row_keep = onehot(gids) @ keep_g
  scratch:     running top-k on the fused score:
                 wsum: ONE (BLK_B, K) list on dense + bm25
                 rrf:  TWO lists (dense, bm25); rank fusion happens in
                       the ops wrapper once the lists exist (ranks only
                       exist after retrieval — the standard RRF form)

FUSION WEIGHTS ARE FOLDED INTO THE INPUTS (`w_dense` into q before the
matmul, `w_lex` into qidf before the gather), so the wsum combine is a
bare ``dense + bm25`` add. This is arena-scan pinning rule 1
(arena_scan/stages.py): a weighted combine at the output is an FMA-
contractible mul+add whose rounding depends on the surrounding fusion —
the historical source of the wsum bit-identity failures. Folding is
value-preserving for ranking (w > 0) and bit-stable across engines
because every engine folds identically.

Isolation is structural exactly as in grouped_topk: the predicate mask
lands on BOTH signals before any merge, so a row outside a group's
predicate can never surface for that group's rows no matter how high its
BM25 score (the lexical-path leakage property, attacked in
tests/test_hybrid.py).

This family is the unified arena-scan framework's lexical configuration
(`repro.kernels.arena_scan`, `ScanSpec(score="fused"|"both")`); the scan
body, both residency regimes (resident BlockSpec pipelining / paged
double-buffered DMA), and the running top-k merges live in the framework.

CPU CI executes this body in interpret mode (bit-identity vs the jnp
refs, at T=64 lanes and QT=16 over a 30,522-id vocabulary too);
tests/test_tpu_compile.py compiles it for a described TPU v5e at D=768,
QT=16 and T=16 or 64, at the wrapper's resident tile (blk_n=512) and at
the planner's 2048-row page. On the chip it runs compiled in
chip_smoke.py (T=16) and, through the served path (RagDB, Scheduler,
executor), at 2^20 x 768 with T=64 lanes of a 30,522-id vocabulary (MS
MARCO widths) on a v5e. The T x QT lexical loop stays unrolled: a
`fori_loop` over lanes read from the VMEM refs compiles faster but took
twice the device time of a pass on a v5e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.kernel import arena_scan_pallas
from repro.kernels.arena_scan.stages import B_LANES, ScanSpec


def hybrid_spec(mode: str) -> ScanSpec:
    """The scan a fusion mode runs: ``wsum`` one fused list, ``rrf`` the
    two per-signal lists."""
    return ScanSpec(score="fused" if mode == "wsum" else "both")


def hybrid_score_pallas(q: jax.Array, emb: jax.Array, meta: jax.Array,
                        terms: jax.Array, lexnorm: jax.Array,
                        gids: jax.Array, preds: jax.Array,
                        qterms: jax.Array, qidf: jax.Array, k: int, *,
                        mode: str = "wsum", w_dense: float = 1.0,
                        w_lex: float = 1.0, blk_b: int = B_LANES,
                        blk_n: int = 512,
                        page_rows: int | None = None,
                        interpret: bool = False):
    """q: (B, D); emb: (N, D); meta: (4, N) int32 lane-major;
    terms/lexnorm: (T, N) lane-major;
    gids: (B, 1) int32; preds: (G, 4) int32; qterms: (B, QT) int32 (-1
    padding); qidf: (B, QT) f32 (0 on padding). B % blk_b == 0, N % blk_n
    == 0 (or N % page_rows == 0 in the paged regime), D % 128 == 0 (the
    ops.py wrapper pads).

    Returns ``wsum``: (fused scores (B, k) f32, slots (B, k) i32);
    ``rrf``: the two per-signal lists (d_s, d_i, l_s, l_i) — rank fusion
    happens post-kernel (weights are unused: RRF ranks are scale-free)."""
    if mode == "wsum":
        # fold fusion weights into the inputs (pinning rule 1)
        q = q * jnp.float32(w_dense)
        qidf = qidf * jnp.float32(w_lex)
    return arena_scan_pallas(q, emb, meta, gids, preds, k,
                             spec=hybrid_spec(mode),
                             lex=(terms, lexnorm, qterms, qidf),
                             blk_b=blk_b, blk_n=blk_n, page_rows=page_rows,
                             interpret=interpret)
