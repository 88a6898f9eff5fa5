"""Pallas TPU kernel: fused IVF probe — the pruned unified query.

The exact scan (kernels/filtered_topk) streams the WHOLE arena HBM->VMEM
every query batch, so p50 grows linearly with corpus size. The probe kernel
scans only the candidate rows named by a predicate group's probed clusters.
The candidate tiles are gathered ONCE per predicate group — the whole batch
of stacked query rows shares one (P, D) stream, never a per-row (B, P, D)
copy. The 5th metadata lane carries each candidate's ARENA slot, so the
running top-k merges slot ids directly: a probe result is always a real
arena row or -1.

Isolation is preserved by construction: the predicate mask is evaluated on
metadata gathered from the ARENA (the single source of truth), not from any
index-side copy — a corrupted/stale member table can only change which rows
get scored, never allow a row that fails the WHERE clause to surface.

This family is the unified arena-scan framework's slot-lane configuration
(`repro.kernels.arena_scan`, `ScanSpec(slot_lane=True)`): the 5th metadata
lane is the output index source and `slot < 0` rows (member-table padding)
are masked in the shared mask stage. Scan body, residency regimes, and the
running top-k merge live in the framework.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.kernel import arena_scan_pallas
from repro.kernels.arena_scan.stages import ScanSpec


def ivf_probe_pallas(q: jax.Array, cand_emb: jax.Array, cand_meta: jax.Array,
                     pred: jax.Array, k: int, *,
                     blk_b: int = 8, blk_p: int = 256,
                     page_rows: int | None = None,
                     interpret: bool = False):
    """q: (B, D); cand_emb: (P, D); cand_meta: (5, P) int32 lane-major
    rows [tenant, ts, cat, acl, arena_slot]; pred: (4,) int32.
    B % blk_b == 0, P % blk_p == 0 (or P % page_rows == 0 in the paged
    regime), D % 128 == 0 (the ops.py wrapper pads).
    Returns (scores (B, k) f32, arena slots (B, k) i32)."""
    B = q.shape[0]
    gids = jnp.zeros((B, 1), jnp.int32)
    s, i = arena_scan_pallas(q, cand_emb, cand_meta, gids,
                             pred[None, :].astype(jnp.int32), k,
                             spec=ScanSpec(score="dense", slot_lane=True),
                             blk_b=blk_b, blk_n=blk_p, page_rows=page_rows,
                             interpret=interpret)
    return s, i
