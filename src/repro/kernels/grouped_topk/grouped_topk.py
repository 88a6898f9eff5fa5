"""Pallas TPU kernel: fused multi-predicate grouped top-k — scan once,
answer every group.

The exact scan (kernels/filtered_topk) runs one predicate over the whole
arena, so a batch carrying G distinct predicate groups streams the arena
HBM->VMEM G times (`rows_scanned = G*N`) and launches G programs. Retrieval
at this scale is memory-bandwidth-bound, so this kernel streams the arena
ONCE for all groups: one score matmul for every group, ALL G predicate
masks in one broadcast pass, each query row selecting ITS group's mask by
one-hot matmul (paper §5: `rows_scanned` drops from G*N to N, and G
compiled programs become 1).

Isolation is structural, exactly as in filtered_topk: a row that fails
group g's predicate is -inf in every g-row's score lane BEFORE the merge,
so it can never reach a g-row's output list — even if it passes another
group's predicate (the cross-group leakage property, tested adversarially).

This family IS the unified arena-scan framework's dense configuration with
G >= 1 predicate groups (`repro.kernels.arena_scan`) — the scan body, the
mask/score stages, both residency regimes, and the running top-k merge all
live there. This module keeps the family's public contract only.
"""
from __future__ import annotations

import jax

from repro.kernels.arena_scan.kernel import arena_scan_pallas
from repro.kernels.arena_scan.stages import B_LANES, ScanSpec


def grouped_topk_pallas(q: jax.Array, emb: jax.Array, meta: jax.Array,
                        gids: jax.Array, preds: jax.Array, k: int, *,
                        blk_b: int = B_LANES, blk_n: int = 512,
                        page_rows: int | None = None,
                        interpret: bool = False):
    """q: (B, D); emb: (N, D); meta: (4, N) int32 lane-major rows
    [tenant, ts, cat, acl];
    gids: (B, 1) int32 group id per query row; preds: (G, 4) int32 stacked
    lowered predicates. B % blk_b == 0, N % blk_n == 0 (or N % page_rows
    == 0 in the paged regime), D % 128 == 0 (the ops.py wrapper pads).
    Returns (scores (B, k) f32, slots (B, k) i32)."""
    s, i = arena_scan_pallas(q, emb, meta, gids, preds, k,
                             spec=ScanSpec(score="dense"),
                             blk_b=blk_b, blk_n=blk_n, page_rows=page_rows,
                             interpret=interpret)
    return s, i
