"""jit'd public wrapper for the filtered_topk kernel.

Handles: metadata packing, padding to tile multiples, CPU interpret-mode
fallback, and the distributed (sharded-corpus) merge:

  corpus rows sharded over a mesh axis
    -> per-shard fused kernel (local top-k)
    -> all_gather of (k per shard) candidates        [tiny: k << N/shard]
    -> final top-k

The gather payload is k rows per shard, so the collective term is O(devices·k)
— independent of corpus size. That IS the paper's scaling story on a TPU pod:
the unified query's cross-device coordination is a constant-size merge, not a
second system.

Padding / packing helpers live in `repro.kernels.arena_scan.ops` (shared by
all four families); `_pack_meta` / `_pad_axis0` stay importable from here.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.arena_scan.ops import (_pack_meta, _pad_axis0,  # noqa: F401
                                          default_blk_b, default_interpret,
                                          pad_dead_rows, pad_d128)
from repro.kernels.arena_scan.stages import B_LANES, ScanSpec
from repro.kernels.filtered_topk.filtered_topk import (NEG_INF,
                                                       filtered_topk_pallas)


@partial(jax.jit, static_argnames=("k", "blk_b", "blk_n", "page_rows",
                                   "interpret"))
def _run(q, emb, meta, pred, k, blk_b, blk_n, page_rows, interpret):
    """Row padding (tenant=-1 dead rows) happens in the caller; here we pad
    D to the 128-lane multiple and B to blk_b (padded D contributes 0 to the
    dot; padded queries are sliced off)."""
    B = q.shape[0]
    q, emb = pad_d128(q, emb)
    q = _pad_axis0(q, blk_b, 0)
    s, i = filtered_topk_pallas(q, emb, meta, pred, k,
                                blk_b=blk_b, blk_n=blk_n,
                                page_rows=page_rows, interpret=interpret)
    return s[:B], i[:B]


def filtered_topk(q, emb, tenant, updated_at, category, acl, pred, k: int,
                  *, blk_b: int | None = None, blk_n: int = 512,
                  page_rows: int | None = None,
                  interpret: bool | None = None):
    """Single-device entry point (contract of core.query.unified_query).
    ``blk_b=None`` takes `default_blk_b`: the whole batch in one query-row
    block, so the arena streams once. ``page_rows`` selects the kernel's
    paged (HBM-resident, double-buffered DMA) regime; bits are unchanged
    (see arena_scan.kernel)."""
    interpret = default_interpret(interpret)
    if blk_b is None:
        blk_b = default_blk_b(q.shape[0], ScanSpec())
    if k > emb.shape[0]:   # LIMIT larger than the arena: SQL semantics
        k_eff = emb.shape[0]
        s, i = filtered_topk(q, emb, tenant, updated_at, category, acl, pred,
                             k_eff, blk_b=blk_b, blk_n=blk_n,
                             page_rows=page_rows, interpret=interpret)
        pad = ((0, 0), (0, k - k_eff))
        return (jnp.pad(s, pad, constant_values=NEG_INF),
                jnp.pad(i, pad, constant_values=-1))
    meta = _pack_meta(tenant, updated_at, category, acl)
    # pad rows *before* jit so padded tenant = -1 (dead rows)
    emb, meta = pad_dead_rows(emb, meta, page_rows or blk_n)
    return _run(q, emb, meta, pred, k, blk_b, blk_n, page_rows, interpret)


def filtered_topk_sharded(mesh: Mesh, axis: str | tuple[str, ...],
                          q, emb, meta, pred, k: int,
                          *, blk_b: int = B_LANES, blk_n: int = 512,
                          interpret: bool | None = None):
    """Distributed unified query over a row-sharded corpus.

    emb (N, D) sharded along its rows and lane-major meta (4, N) along its
    columns over ``axis``; q replicated.
    Returns (scores (B, k), GLOBAL slots (B, k)).
    """
    interpret = default_interpret(interpret)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    n_local = emb.shape[0] // n_shards

    blk_n_l = min(blk_n, n_local)
    assert n_local % blk_n_l == 0, (n_local, blk_n_l)

    def local_fn(q_l, emb_l, meta_l, pred_l):
        shard_id = jax.lax.axis_index(axes)
        B = q_l.shape[0]
        q_pad = _pad_axis0(q_l, blk_b, 0)
        s, i = filtered_topk_pallas(q_pad, emb_l, meta_l, pred_l, k,
                                    blk_b=blk_b, blk_n=blk_n_l, interpret=interpret)
        s, i = s[:B], i[:B]
        i = jnp.where(i >= 0, i + shard_id * n_local, -1)
        # constant-size merge: k candidates per shard
        s_all = jax.lax.all_gather(s, axes, axis=1, tiled=True)   # (B, shards*k)
        i_all = jax.lax.all_gather(i, axes, axis=1, tiled=True)
        top_s, pos = jax.lax.top_k(s_all, k)
        top_i = jnp.take_along_axis(i_all, pos, axis=1)
        return top_s, jnp.where(top_s > jnp.float32(NEG_INF), top_i, -1)

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(), P(axes), P(None, axes), P()),
                       out_specs=(P(), P()),
                       check_vma=False)  # pallas outs carry no vma info
    return fn(q, emb, meta, pred)
