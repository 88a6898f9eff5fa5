"""Shared wrapper plumbing for every arena-scan family.

The four family ops modules (`filtered_topk`, `ivf_probe`, `grouped_topk`,
`hybrid_score`) keep their public contracts but all pad / pack / dispatch
through these helpers, so the invariants live in exactly one place:

  * arena rows pad to the tile (or page) multiple as DEAD rows
    (tenant = -1, term lanes empty, lexnorm 0) for EVERY engine, so
    kernel, scan, and oracle run on identical arrays and bit-identity is
    testable;
  * D pads to the 128-lane MXU multiple (padded dims contribute 0 to the
    dot), B pads to the blk_b multiple (row-parallel: padding rows cannot
    perturb real rows, and they are sliced off before returning), with
    blk_b from `default_blk_b`;
  * the metadata columns and the (N, T) lexical lanes are packed
    LANE-MAJOR — (4, N) meta, (T, N) lanes — once per snapshot and
    LRU-memoized on the column object ids (snapshot columns are immutable
    — a write is only observable through NEW column arrays). Lane-major is
    what lets a page DMA slice the narrow streams along a 128-aligned axis.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.stages import B_LANES, ScanSpec

#: jnp streaming-scan tile: big enough that tile overhead (local top-k,
#: scan step) amortizes, small enough that a tile's scores stay cache-close.
BLK_SCAN = 32768


def _pack_meta(tenant, updated_at, category, acl):
    """Lane-major (4, N) int32 metadata: rows tenant, updated_at, category,
    acl (bit pattern)."""
    return jnp.stack([tenant.astype(jnp.int32), updated_at.astype(jnp.int32),
                      category.astype(jnp.int32), acl.astype(jnp.int32)],
                     axis=0)


#: Packed-lane memo: keyed on the source arrays' object ids; entries HOLD
#: the sources so a key can never alias a freed array, and the tiny LRU
#: bounds that retention to a few snapshots' worth of narrow columns (the
#: embedding matrix is never held).
_META_CACHE: OrderedDict[tuple, tuple] = OrderedDict()
_META_CACHE_CAP = 4


def _memo_packed(fn, *cols):
    if any(isinstance(c, jax.core.Tracer) for c in cols):
        return fn(*cols)            # under an outer jit: nothing to reuse
    key = (fn.__name__,) + tuple(id(c) for c in cols)
    hit = _META_CACHE.get(key)
    if hit is not None:
        _META_CACHE.move_to_end(key)
        return hit[0]
    packed = fn(*cols)
    _META_CACHE[key] = (packed,) + cols
    while len(_META_CACHE) > _META_CACHE_CAP:
        _META_CACHE.popitem(last=False)
    return packed


def _packed_meta(tenant, updated_at, category, acl):
    """`_pack_meta`, memoized per snapshot."""
    return _memo_packed(_pack_meta, tenant, updated_at, category, acl)


def _lanes_t(terms, lexnorm):
    return (jnp.asarray(terms, jnp.int32).T,
            jnp.asarray(lexnorm, jnp.float32).T)


def _packed_lanes(terms, lexnorm):
    """(N, T) lexical lanes -> lane-major (T, N) pair, memoized per
    snapshot."""
    return _memo_packed(_lanes_t, terms, lexnorm)


def _pad_axis0(x, mult, fill):
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def _pad_cols(x, mult, fill):
    pad = (-x.shape[1]) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)), constant_values=fill)


def pad_dead_rows(emb, meta, mult: int, terms=None, lexnorm=None):
    """Pad the arena streams to the tile multiple with DEAD rows
    (tenant = -1 — no predicate group can keep them; slot-lane metas also
    get slot = -1). ``meta`` and the lexical lanes are lane-major (W, N):
    their rows pad along axis 1. Returns the padded streams."""
    n = emb.shape[0]
    emb = _pad_axis0(emb, mult, 0)
    meta = _pad_cols(meta, mult, 0)
    if meta.shape[1] != n:
        dead_col = jnp.zeros((meta.shape[0],), jnp.int32).at[0].set(-1)
        if meta.shape[0] > 4:
            dead_col = dead_col.at[4].set(-1)
        dead = jnp.arange(meta.shape[1]) >= n
        meta = jnp.where(dead[None, :], dead_col[:, None], meta)
    if terms is None:
        return emb, meta
    return (emb, meta, _pad_cols(terms, mult, -1),
            _pad_cols(lexnorm, mult, 0))


def pad_d128(q, emb):
    """Pad the contraction axis to the 128-lane MXU multiple (padded dims
    contribute 0.0 to every dot product)."""
    d_pad = (-q.shape[1]) % 128
    if d_pad:
        q = jnp.pad(q, ((0, 0), (0, d_pad)))
        emb = jnp.pad(emb, ((0, 0), (0, d_pad)))
    return q, emb


def default_use_kernel(use_kernel: bool | None) -> bool:
    """Pallas on a TPU backend, the jnp streaming scan elsewhere. There is
    no fallback the other way: on a TPU the compiled kernel runs or the
    call raises."""
    if use_kernel is None:
        return jax.default_backend() == "tpu"
    return use_kernel


def default_interpret(interpret: bool | None) -> bool:
    """Interpret-mode Pallas off a TPU (tests), compiled Mosaic on one."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def default_blk_n(n: int, use_kernel: bool, page_rows: int | None = None) -> int:
    """Tile-size policy: the kernel's VMEM tile is 512 rows; the jnp scan
    uses `BLK_SCAN` clamped to the pow2 arena bucket so small stores stay
    single-tile. An explicit ``page_rows`` (the planner's paged-regime
    knob) overrides both — the scan tile IS the page."""
    if page_rows is not None:
        return page_rows
    if use_kernel:
        return 512
    cap = 1 << max(int(n) - 1, 0).bit_length()
    return min(BLK_SCAN, max(cap, 1))


#: Largest query-row block: the MXU's width. A batch over it is split into
#: blocks of this many rows by the kernel's grid, one arena stream each.
MAX_BLK_B = 128


def default_blk_b(b: int, spec: ScanSpec) -> int:
    """Query-row block policy: the kernel streams the arena once per block
    of ``blk_b`` query rows. A dense scan takes the launch's whole batch of
    ``b`` rows as one block (rounded up to the `B_LANES` pad multiple,
    capped at `MAX_BLK_B`): its MXU cost per arena tile hardly depends on
    the rows, so every extra block is a whole extra stream of the arena. A
    scan with a lexical stage keeps `B_LANES`: its BM25 compare loop is VPU
    work per (query row, arena row), which a wider block does not share."""
    if spec.has_lex:
        return B_LANES
    return min(-(-max(b, 1) // B_LANES) * B_LANES, MAX_BLK_B)
