"""Pallas TPU kernel: fused filtered similarity top-k — the unified query.

One pass over the corpus arena does ALL of the paper's unified SQL statement:
similarity (MXU dot) + engine-level WHERE (VPU predicate mask) + running
ORDER BY .. LIMIT k (VMEM scratch merge). A row that fails the WHERE clause
can never reach the output buffer — the kernel-level equivalent of row-level
security, and the structural reason tenant leakage is impossible (paper
Table 3).

This family is the simplest configuration of the unified arena-scan
framework (`repro.kernels.arena_scan`): the default dense `ScanSpec` with a
single predicate group — every query row selects group 0. The scan body,
tiling regimes (resident BlockSpec pipelining and paged double-buffered
DMA), and the running top-k merge all live in the framework; this module
only adapts the single-predicate contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.kernel import arena_scan_pallas
from repro.kernels.arena_scan.stages import (B_LANES, NEG_INF,  # noqa: F401
                                             ScanSpec,
                                             merge_topk as _merge_topk)


def filtered_topk_pallas(q: jax.Array, emb: jax.Array, meta: jax.Array,
                         pred: jax.Array, k: int, *,
                         blk_b: int = B_LANES, blk_n: int = 512,
                         page_rows: int | None = None,
                         interpret: bool = False):
    """q: (B, D); emb: (N, D); meta: (4, N) int32 lane-major rows
    [tenant, ts, cat, acl];
    pred: (4,) int32. B % blk_b == 0, N % blk_n == 0 (or N % page_rows == 0
    in the paged regime), D % 128 == 0 (the ops.py wrapper pads). Returns
    (scores (B, k) f32, slots (B, k) i32)."""
    B = q.shape[0]
    gids = jnp.zeros((B, 1), jnp.int32)
    s, i = arena_scan_pallas(q, emb, meta, gids,
                             pred[None, :].astype(jnp.int32), k,
                             spec=ScanSpec(score="dense"),
                             blk_b=blk_b, blk_n=blk_n, page_rows=page_rows,
                             interpret=interpret)
    return s, i
