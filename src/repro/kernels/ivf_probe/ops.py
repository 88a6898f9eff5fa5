"""jit'd public wrapper for the ivf_probe kernel.

Handles candidate assembly + padding + engine dispatch:

  probed cluster ids (deduplicated union for ONE predicate group)
    -> member-table rows (U, cap) + the exact-scan overflow tail
    -> ONE (P, D) embedding / (5, P) metadata gather for the whole group
    -> fused probe (Pallas on TPU, jnp ref elsewhere): mask + score + running
       top-k over arena slots

The gather is per GROUP: B stacked query rows share one (P, D) candidate
stream. No code path materializes a per-row (B, P, D) copy — that gather is
what made the old jnp probe slower than the exact scan it was pruning.

Metadata (and embeddings) are gathered from the ARENA columns, never from an
index-side copy: the predicate mask always sees the authoritative row, so a
stale or adversarially poisoned member table can only waste score work —
rows that fail the WHERE clause stay unreturnable (slot ids outside the
arena are dropped at assembly).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.ops import (_pad_axis0, default_interpret,
                                          default_use_kernel, pad_d128,
                                          pad_dead_rows)
from repro.kernels.ivf_probe.ivf_probe import ivf_probe_pallas
from repro.kernels.ivf_probe.ref import NEG_INF, ivf_probe_ref


def _assemble(emb, tenant, updated_at, category, acl, members, overflow,
              clusters):
    """Candidate rows for one predicate group: the probed clusters' member
    slots plus the overflow tail, with arena-side metadata. Returns
    (cand_emb (P, D), cand_meta (5, P) int32, lane-major)."""
    n = emb.shape[0]
    m = members[jnp.maximum(clusters, 0)]                  # (U, cap)
    m = jnp.where((clusters >= 0)[:, None], m, -1)         # cluster-list pad
    cand = jnp.concatenate([m.reshape(-1), overflow])      # (P,)
    # out-of-range slots (poisoned/corrupt member table) are dead, not clamped
    cand = jnp.where((cand >= 0) & (cand < n), cand, -1)
    safe = jnp.maximum(cand, 0)
    meta = jnp.stack([
        jnp.where(cand >= 0, tenant[safe], -1),
        updated_at[safe],
        category[safe],
        acl[safe].astype(jnp.int32),
        cand,
    ], axis=0)
    return emb[safe], meta


@partial(jax.jit, static_argnames=("k", "use_kernel", "blk_b", "blk_p",
                                   "interpret"))
def _run(q, emb, tenant, updated_at, category, acl, members, overflow,
         clusters, pred, k, use_kernel, blk_b, blk_p, interpret):
    cand_emb, cand_meta = _assemble(emb, tenant, updated_at, category, acl,
                                    members, overflow, clusters)
    # pad P to the block multiple with dead rows (tenant -1, slot -1) for
    # BOTH engines, so kernel and ref run on identical arrays
    # (bit-identity is testable)
    cand_emb, cand_meta = pad_dead_rows(cand_emb, cand_meta, blk_p)
    if not use_kernel:
        return ivf_probe_ref(q, cand_emb, cand_meta, pred, k)
    B = q.shape[0]
    q, cand_emb = pad_d128(q, cand_emb)
    q = _pad_axis0(q, blk_b, 0)
    s, i = ivf_probe_pallas(q, cand_emb, cand_meta, pred, k,
                            blk_b=blk_b, blk_p=blk_p, interpret=interpret)
    return s[:B], i[:B]


def ivf_probe(q, emb, tenant, updated_at, category, acl, members, overflow,
              clusters, pred, k: int, *, use_kernel: bool | None = None,
              blk_b: int = 8, blk_p: int = 256,
              interpret: bool | None = None):
    """Fused probe over one predicate group's candidate set.

    q: (B, D) stacked query rows; emb/tenant/updated_at/category/acl: the
    ARENA columns (source of truth); members: (C, cap) i32 member table;
    overflow: (O,) i32 exact-scan tail; clusters: (U,) i32 probed cluster
    ids, -1-padded to a bucketed length; pred: (4,) int32.
    Returns (scores (B, k) f32, ARENA slots (B, k) i32, -1 past the fill).

    ``use_kernel=None`` picks the Pallas kernel on a TPU backend and the jnp
    ref elsewhere; tests pass ``use_kernel=True, interpret=True`` to execute
    the kernel body on CPU.
    """
    use_kernel = default_use_kernel(use_kernel)
    interpret = default_interpret(interpret)
    n_cand = members.shape[1] * clusters.shape[0] + overflow.shape[0]
    n_cand_padded = n_cand + ((-n_cand) % blk_p)
    if n_cand_padded == 0:          # empty candidate set: nothing qualifies
        B = q.shape[0]
        return (jnp.full((B, k), NEG_INF, jnp.float32),
                jnp.full((B, k), -1, jnp.int32))
    if k > n_cand_padded:   # LIMIT larger than the candidate set: SQL semantics
        k_eff = n_cand_padded
        s, i = ivf_probe(q, emb, tenant, updated_at, category, acl, members,
                         overflow, clusters, pred, k_eff, use_kernel=use_kernel,
                         blk_b=blk_b, blk_p=blk_p, interpret=interpret)
        pad = ((0, 0), (0, k - k_eff))
        return (jnp.pad(s, pad, constant_values=NEG_INF),
                jnp.pad(i, pad, constant_values=-1))
    return _run(jnp.asarray(q), emb, tenant, updated_at, category, acl,
                members, overflow, jnp.asarray(clusters, jnp.int32), pred,
                k, use_kernel, blk_b, blk_p, interpret)
