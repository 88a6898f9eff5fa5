"""Pure-jnp oracle for the ivf_probe kernel.

Contract shared with the Pallas kernel (ivf_probe.py): score ONLY the
candidate rows a predicate group's probed clusters name, apply the
engine-level predicate in the same pass, and return ARENA slots — the
probe changes which rows are *scored*, never which rows may be *returned*.

Both engines are the arena-scan framework's slot-lane jnp engines
(`repro.kernels.arena_scan.ref`); bit-identity with the Pallas kernel is
structural (shared stages).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.ref import arena_scan_ref, arena_scan_scan_ref
from repro.kernels.arena_scan.stages import ScanSpec

NEG_INF = jnp.float32(jnp.finfo(jnp.float32).min)

_SPEC = ScanSpec(score="dense", slot_lane=True)


@partial(jax.jit, static_argnames=("k",))
def ivf_probe_ref(q: jax.Array, cand_emb: jax.Array, cand_meta: jax.Array,
                  pred: jax.Array, k: int):
    """q: (B, D); cand_emb: (P, D) — the probed clusters' member rows,
    gathered ONCE for the whole predicate group (never per query row);
    cand_meta: (5, P) int32 lane-major rows [tenant, updated_at, category,
    acl, arena_slot]
    (slot < 0 marks member-table padding); pred: (4,) int32.
    Returns (scores (B, k) f32, arena slots (B, k) i32, -1 past the fill)."""
    gids = jnp.zeros((q.shape[0],), jnp.int32)
    s, i = arena_scan_ref(q, cand_emb, cand_meta, gids,
                          pred[None, :].astype(jnp.int32), k, spec=_SPEC)
    return s, i


@partial(jax.jit, static_argnames=("k", "blk_p"))
def ivf_probe_scan_ref(q: jax.Array, cand_emb: jax.Array,
                       cand_meta: jax.Array, pred: jax.Array, k: int,
                       blk_p: int):
    """Streaming jnp probe: the kernel's tile schedule without Pallas
    (P % blk_p == 0; the ops.py wrapper pads). Bit-identical to
    `ivf_probe_ref` by the arena-scan construction."""
    gids = jnp.zeros((q.shape[0],), jnp.int32)
    s, i = arena_scan_scan_ref(q, cand_emb, cand_meta, gids,
                               pred[None, :].astype(jnp.int32), k, blk_p,
                               spec=_SPEC)
    return s, i
