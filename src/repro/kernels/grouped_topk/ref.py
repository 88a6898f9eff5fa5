"""Pure-jnp oracle for the grouped_topk kernel.

Contract shared with the Pallas kernel (grouped_topk.py): ONE pass over the
arena answers EVERY predicate group in the batch. The G lowered predicates
are evaluated as G masks over the same metadata columns, and each query row
selects its own group's mask by group id — so a row can only ever surface
arena rows that satisfy ITS group's predicate, never another group's (the
kernel-level multi-tenant isolation claim, property-tested in
tests/test_grouped_topk.py).

Both engines here are the arena-scan framework's dense jnp engines
(`repro.kernels.arena_scan.ref`) under this family's contract; bit-identity
with the Pallas kernel is structural (shared stages — see
arena_scan/stages.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.ref import arena_scan_ref, arena_scan_scan_ref
from repro.kernels.arena_scan.stages import ScanSpec, predicate_keep

NEG_INF = jnp.float32(jnp.finfo(jnp.float32).min)


def group_masks(meta: jax.Array, preds: jax.Array) -> jax.Array:
    """All G engine-level WHERE clauses over one metadata block, one pass.

    meta: (4, N) int32 lane-major rows [tenant, updated_at, category, acl];
    preds: (G, 4) int32 stacked `Predicate.as_array()` rows.
    Returns (G, N) bool — row n is live AND satisfies group g's clauses.
    (Alias of the framework's `predicate_keep` mask stage.)
    """
    return predicate_keep(meta, preds)


@partial(jax.jit, static_argnames=("k",))
def grouped_topk_ref(q: jax.Array, emb: jax.Array, meta: jax.Array,
                     gids: jax.Array, preds: jax.Array, k: int):
    """Dense oracle. q: (B, D); emb: (N, D); meta: (4, N) int32; gids: (B,)
    int32 group id per query row (values in [0, G)); preds: (G, 4) int32.
    Returns (scores (B, k) f32, slots (B, k) i32, -1 past the fill)."""
    s, i = arena_scan_ref(q, emb, meta, gids, preds, k,
                          spec=ScanSpec(score="dense"))
    return s, i


@partial(jax.jit, static_argnames=("k", "blk_n"))
def grouped_topk_scan_ref(q: jax.Array, emb: jax.Array, meta: jax.Array,
                          gids: jax.Array, preds: jax.Array, k: int,
                          blk_n: int):
    """Streaming jnp implementation — the kernel's schedule without Pallas:
    scan the arena in (blk_n, D) tiles, mask + score + LOCAL top-k per tile,
    one final merge over the (T*k)-wide candidate list. Never materializes
    the (B, N) score matrix, so the arena streams once at memory speed —
    on a CPU rig this is what makes the fused scan beat the per-group loop
    (the Pallas kernel does the same with VMEM scratch on TPU).

    BIT-identical to `grouped_topk_ref` by construction — the framework's
    streaming engine runs the same stage functions per tile, tiling splits
    N only, and `lax.top_k` breaks ties toward the lower index locally and
    in the merge. N % blk_n == 0 (ops.py pads)."""
    s, i = arena_scan_scan_ref(q, emb, meta, gids, preds, k, blk_n,
                               spec=ScanSpec(score="dense"))
    return s, i
