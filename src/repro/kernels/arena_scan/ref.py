"""The unified arena scan's jnp engines: dense oracle + streaming scan.

Both are generic over the same `ScanSpec` as the Pallas kernel and run the
same `stages` functions per tile, which is what makes the three engines
bit-identical (see stages.py). The oracle materializes the full (B, N)
score block (the ground truth the conformance matrix pins everything to);
the streaming scan is the kernel's schedule without Pallas — tiles of
blk_n rows, local top-k per tile, one final merge — and is the production
engine on the CPU rig (kernels run interpret-mode there, far too slow to
serve). The scan's blk_n IS the page size: the paged Pallas kernel at
page_rows = P merges in exactly this schedule at blk_n = P.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.stages import (B_LANES, NEG_INF, ScanSpec,
                                             tile_mask, tile_signals)


def _finish(top_s, top_i, k: int, k_eff: int):
    if k_eff < k:
        pad = ((0, 0), (0, k - k_eff))
        top_s = jnp.pad(top_s, pad, constant_values=NEG_INF)
        top_i = jnp.pad(top_i, pad, constant_values=-1)
    return top_s, jnp.where(top_s > NEG_INF, top_i, -1)


def _pad_b(q, gids, lex):
    """Pad the query block to the `B_LANES` lane width (pinning rule 3:
    the contraction shape must match the kernel's in every engine). Zero
    query rows with group id 0 and no query terms; the caller slices the
    outputs back to B."""
    b = q.shape[0]
    bp = -(-b // B_LANES) * B_LANES
    if bp == b:
        return q, gids, lex
    pad = bp - b
    q = jnp.pad(q, ((0, pad), (0, 0)))
    gids = jnp.pad(gids, (0, pad))
    if lex is not None:
        terms, lexnorm, qterms, qidf = lex
        lex = (terms, lexnorm,
               jnp.pad(qterms, ((0, pad), (0, 0)), constant_values=-1),
               jnp.pad(qidf, ((0, pad), (0, 0))))
    return q, gids, lex


def arena_scan_ref(q, emb, meta, gids, preds, k: int, *,
                   spec: ScanSpec = ScanSpec(), lex: tuple | None = None):
    """Dense oracle. Same contract as `arena_scan_pallas` (gids is (B,)
    here — the gather form; boolean-identical to the kernel's one-hot
    select). Returns `spec.n_lists` (scores (B, k'), indices (B, k'))
    pairs flattened, k' = min(k, N) padded back to k."""
    n = emb.shape[0]
    b = q.shape[0]
    q, gids, lex = _pad_b(q, gids, lex)
    row_keep = tile_mask(spec, meta, preds, gids, onehot=False)
    signals = tile_signals(spec, q, emb, row_keep, lex, barrier=True)
    if spec.slot_lane:
        idx_src = meta[4]
    else:
        idx_src = jnp.arange(n, dtype=jnp.int32)
    k_eff = min(k, n)
    out = []
    for sig in signals:
        top_s, pos = jax.lax.top_k(sig, k_eff)
        top_i = jnp.take_along_axis(
            jnp.broadcast_to(idx_src[None, :], sig.shape), pos, axis=1)
        out.extend(a[:b] for a in _finish(top_s, top_i, k, k_eff))
    return tuple(out)


def arena_scan_scan_ref(q, emb, meta, gids, preds, k: int, blk_n: int, *,
                        spec: ScanSpec = ScanSpec(),
                        lex: tuple | None = None):
    """Streaming scan: `lax.scan` over (blk_n,)-row tiles, LOCAL top-k per
    running list, one final merge over the (tiles*k)-wide candidates.
    Never materializes (B, N). N % blk_n == 0 (family ops pad). ``meta``
    and the lexical lanes are lane-major (W, N), as in every engine.

    Bit-identity with the oracle is by construction: same stage functions,
    tiling splits N only, and `lax.top_k` breaks ties toward the lower
    index locally and in the merge (candidates concatenate in tile order),
    so tied scores pick the same rows as the oracle's single top_k."""
    n = emb.shape[0]
    b = q.shape[0]
    q, gids, lex = _pad_b(q, gids, lex)
    assert n % blk_n == 0, (n, blk_n)
    n_tiles = n // blk_n

    def tiled(lanes):          # lane-major (W, N) -> (tiles, W, blk_n)
        return jnp.moveaxis(lanes.reshape(lanes.shape[0], n_tiles, blk_n),
                            1, 0)

    emb_t = emb.reshape(n_tiles, blk_n, emb.shape[1])
    base_t = jnp.arange(n_tiles, dtype=jnp.int32) * blk_n
    tiles = (emb_t, tiled(meta), base_t)
    if spec.has_lex:
        terms, lexnorm, qterms, qidf = lex
        tiles += (tiled(terms), tiled(lexnorm))
    k_loc = min(k, blk_n)

    def step(_, tile):
        e, m, base = tile[:3]
        lex_tile = (tile[3], tile[4], qterms, qidf) if spec.has_lex else None
        row_keep = tile_mask(spec, m, preds, gids, onehot=False)
        signals = tile_signals(spec, q, e, row_keep, lex_tile, barrier=True)
        if spec.slot_lane:
            idx_src = jnp.broadcast_to(m[4:5, :], signals[0].shape)
        out = []
        for sig in signals:
            s, pos = jax.lax.top_k(sig, k_loc)
            if spec.slot_lane:
                out += [s, jnp.take_along_axis(idx_src, pos, axis=1)]
            else:
                out += [s, base + pos]
        return None, tuple(out)

    def merge(loc_s, loc_i):
        all_s = jnp.moveaxis(loc_s, 0, 1).reshape(q.shape[0], -1)
        all_i = jnp.moveaxis(loc_i, 0, 1).reshape(q.shape[0], -1)
        k_eff = min(k, all_s.shape[1])
        top_s, sel = jax.lax.top_k(all_s, k_eff)
        top_i = jnp.take_along_axis(all_i, sel, axis=1)
        return _finish(top_s, top_i, k, k_eff)

    _, locs = jax.lax.scan(step, None, tiles)
    out = []
    for j in range(spec.n_lists):
        out.extend(a[:b] for a in merge(locs[2 * j], locs[2 * j + 1]))
    return tuple(out)
