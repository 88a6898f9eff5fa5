"""Shard-mapped arena scan — the `sharded` engine's device program.

The arena is row-sharded over a `jax.sharding.Mesh` in contiguous,
slot-aligned regions (`repro.core.store.ShardPlacement`); `shard_map` runs
the SAME arena-scan stages (stages.py) per shard, each shard keeps only its
local (B, k) best, and the only cross-device traffic is an all-gather of the
per-shard (scores, doc_ids, slots) k-lists — O(S·B·k) wire bytes, constant
in corpus size, instead of the O(B·N) score matrix a naive GSPMD lowering of
the dense oracle would gather. `collective_bytes_of_hlo` verifies that bound
against the compiled HLO (see tools in distributed/collectives.py).

Determinism contract (placement invariance): every selection — the local
top-k AND the cross-shard merge — is exact lexicographic
(score desc, global doc_id asc). A tie-break by slot or gathered column
position would depend on WHERE rows landed; breaking by global doc id makes
the returned k-list a pure function of the corpus, so shuffling the shard
assignment (or changing S) cannot change results bit-wise
(tests/test_distributed.py pins this property).

Tenant-affine audit: under a ``"tenant"`` placement a tenant-scoped
predicate names its owning shard statically (tenant % S), so every other
shard skips its scan entirely via `lax.cond` — structural isolation, not
just masking — and the program returns a per-shard ``rows_scanned`` vector
so the skip is auditable from `ExecStats` / `explain()`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.arena_scan.ref import _pad_b
from repro.kernels.arena_scan.stages import (NEG_INF, ScanSpec, tile_mask,
                                             tile_signals)

INT32_MAX = jnp.iinfo(jnp.int32).max


def lex_topk(scores: jax.Array, doc_ids: jax.Array, k: int):
    """Exact lexicographic (score desc, doc_id asc) top-k over columns.

    scores: (B, n) f32 (masked rows NEG_INF); doc_ids: (n,) int32, unique
    among rows with score > NEG_INF. Returns (scores (B,k), doc_ids (B,k),
    positions (B,k)); entries beyond the qualifying rows are
    (NEG_INF, INT32_MAX, -1).

    `lax.top_k` alone breaks ties by column position, which is placement-
    dependent. Instead of a full O(n log n) sort, select an O(k)-wide
    candidate set and sort only that:

      * A' — entries STRICTLY above the kth-largest score. Every such entry
        is inside `top_k`'s output (if x > kth and x were outside the top k,
        the top k would hold k values >= x > kth — contradiction), and there
        are at most k-1 of them, so A' is complete by construction.
      * B  — the k smallest doc ids among entries TIED at the kth score
        (a second `top_k` over negated, masked ids). Any tied entry the
        lexicographic order admits must be one of the k id-smallest ties.

    A' and B are disjoint (strict vs equal), their union contains the true
    lexicographic top-k, and a 2-key `lax.sort` over the 2k candidates
    finishes the selection.
    """
    b, n = scores.shape
    ids_b = jnp.broadcast_to(doc_ids[None, :], (b, n))
    if n <= k:
        pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (b, n))
        neg_s, d, p = jax.lax.sort((-scores, ids_b, pos), num_keys=2)
        pad = ((0, 0), (0, k - n))
        return (jnp.pad(-neg_s, pad, constant_values=NEG_INF),
                jnp.pad(d, pad, constant_values=INT32_MAX),
                jnp.pad(p, pad, constant_values=-1))
    top_s, top_pos = jax.lax.top_k(scores, k)                      # (B, k)
    kth = top_s[:, k - 1:k]                                        # (B, 1)
    gt = top_s > kth
    a_s = jnp.where(gt, top_s, NEG_INF)
    a_d = jnp.where(gt, jnp.take_along_axis(ids_b, top_pos, axis=1), INT32_MAX)
    a_p = jnp.where(gt, top_pos, -1)
    tie = scores == kth                                            # (B, n)
    tie_ids = jnp.where(tie, ids_b, INT32_MAX)
    neg_top, tie_pos = jax.lax.top_k(-tie_ids, k)                  # k smallest ids
    b_d = -neg_top
    valid = b_d < INT32_MAX
    b_s = jnp.where(valid, kth, NEG_INF)
    b_p = jnp.where(valid, tie_pos, -1)
    cand = (jnp.concatenate([-a_s, -b_s], axis=1),
            jnp.concatenate([a_d, b_d], axis=1),
            jnp.concatenate([a_p, b_p], axis=1))
    neg_s, d, p = jax.lax.sort(cand, num_keys=2)
    return -neg_s[:, :k], d[:, :k], p[:, :k]


def lex_merge(scores: jax.Array, doc_ids: jax.Array, slots: jax.Array, k: int):
    """Merge gathered per-shard k-lists (B, S*k) under the same
    (score desc, doc_id asc) order: one 2-key sort over the S*k candidates.
    Slots of non-qualifying entries come back -1."""
    neg_s, d, sl = jax.lax.sort((-scores, doc_ids, slots), num_keys=2)
    top_s = -neg_s[:, :k]
    return top_s, jnp.where(top_s > NEG_INF, sl[:, :k], -1)


def make_sharded_arena_scan(mesh, axes, n_rows: int, k: int, *,
                            placement_kind: str = "hash"):
    """Build the shard-mapped unified query over a row-sharded hot arena.

    Returns ``fn(store, q, pred) -> (scores (B, k), slots (B, k),
    rows_scanned (S,))``: globally top-k results bit-identical to the dense
    oracle's (score, doc_id)-lexicographic selection on the unsharded arena,
    plus the per-shard scanned-row audit vector. ``placement_kind="tenant"``
    enables the affine shard-skip gate (the arena must actually be placed
    tenant-affine — `ShardPlacement(kind="tenant")` — for it to be sound).
    """
    ax = (axes,) if isinstance(axes, str) else tuple(axes)
    n_shards = 1
    for a in ax:
        n_shards *= mesh.shape[a]
    if n_rows % n_shards:
        raise ValueError(f"n_rows {n_rows} not divisible by {n_shards} shards")
    n_local = n_rows // n_shards
    spec = ScanSpec()                                   # dense, no slot lane
    affine = placement_kind == "tenant"

    def local_fn(store_l, q_l, pred_l):
        sid = jax.lax.axis_index(ax)
        b = q_l.shape[0]
        q_p, gids, _ = _pad_b(q_l, jnp.zeros((b,), jnp.int32), None)
        bp = q_p.shape[0]

        def scan_shard(_):
            meta = jnp.stack([store_l["tenant"].astype(jnp.int32),
                              store_l["updated_at"].astype(jnp.int32),
                              store_l["category"].astype(jnp.int32),
                              store_l["acl"].astype(jnp.int32)], axis=0)
            row_keep = tile_mask(spec, meta, pred_l[None, :], gids,
                                 onehot=False)
            sig, = tile_signals(spec, q_p, store_l["emb"], row_keep,
                                barrier=True)
            s, d, pos = lex_topk(sig, store_l["doc_id"], k)
            slots = jnp.where(pos >= 0, pos + sid * n_local, -1)
            return s, d, slots, jnp.full((1,), n_local, jnp.int32)

        def skip_shard(_):
            return (jnp.full((bp, k), NEG_INF, jnp.float32),
                    jnp.full((bp, k), INT32_MAX, jnp.int32),
                    jnp.full((bp, k), -1, jnp.int32),
                    jnp.zeros((1,), jnp.int32))

        if affine:
            # tenant-affine shard skip: a tenant-scoped query (tenant >= 0)
            # owns exactly one shard; every other shard's scan never runs.
            tenant_q = pred_l[0]
            active = (tenant_q < 0) | (tenant_q % n_shards == sid)
            s, d, slots, rows = jax.lax.cond(active, scan_shard, skip_shard,
                                             None)
        else:
            s, d, slots, rows = scan_shard(None)

        # the ONLY collectives: three (B, k) all-gathers — O(S·B·k) bytes
        s_all = jax.lax.all_gather(s, ax, axis=1, tiled=True)
        d_all = jax.lax.all_gather(d, ax, axis=1, tiled=True)
        sl_all = jax.lax.all_gather(slots, ax, axis=1, tiled=True)
        top_s, top_sl = lex_merge(s_all, d_all, sl_all, k)
        return top_s[:b], top_sl[:b], rows

    row = P(ax)
    store_specs = {"emb": P(ax, None), "tenant": row, "category": row,
                   "updated_at": row, "acl": row, "doc_id": row,
                   "version": row, "commit_ts": P(), "n_live": P()}
    return jax.jit(jax.shard_map(local_fn, mesh=mesh,
                                 in_specs=(store_specs, P(), P()),
                                 out_specs=(P(), P(), P(ax)),
                                 check_vma=False))


def sharded_collective_bytes(fn, store, q, pred) -> int:
    """Total collective wire bytes of ``fn``'s compiled HLO for the given
    argument shapes (the O(S·B·k) payload the bench lane asserts)."""
    from repro.distributed.collectives import collective_bytes_of_hlo
    sds = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        (store, q, pred))
    txt = jax.jit(fn).lower(*sds).compile().as_text()
    return sum(collective_bytes_of_hlo(txt).values())
