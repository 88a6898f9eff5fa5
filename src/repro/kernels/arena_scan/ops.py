"""The arena scan's one public entry, `arena_scan`, and the plumbing it owns.

Every retrieval scan above the kernel layer calls `arena_scan` with a
`ScanSpec`; the scan families are presets of it, not packages:

  * filtered — the dense ``ScanSpec()`` with one predicate group (G = 1);
  * grouped  — ``ScanSpec()`` with G >= 1 groups, one per query row's
    ``gids`` entry;
  * hybrid   — ``stages.hybrid_spec(mode)`` with the lexical lanes, the idf
    table and the query terms;
  * ivf      — ``ScanSpec(slot_lane=True)`` over a candidate set
    (``candidates``; `repro.core.ivf` names the rows).

The entry owns each of these once, for every family:

  * LIMIT past the rows scanned: SQL semantics, padded to k;
  * the metadata columns and the (N, T) lexical lanes are packed
    LANE-MAJOR — (4, N) meta, (T, N) lanes — once per snapshot and
    LRU-memoized on the column object ids (snapshot columns are immutable
    — a write is only observable through NEW column arrays). Lane-major is
    what lets a page DMA slice the narrow streams along a 128-aligned axis;
  * arena rows pad to the tile (or page) multiple as DEAD rows
    (tenant = -1, term lanes empty, lexnorm 0) for EVERY engine, so
    kernel, scan, and oracle run on identical arrays and bit-identity is
    testable;
  * the engine: the Pallas kernel or the jnp streaming scan
    (`default_use_kernel`, `default_interpret`);
  * D pads to the 128-lane MXU multiple (padded dims contribute 0 to the
    dot), B pads to the blk_b multiple (row-parallel: padding rows cannot
    perturb real rows, and they are sliced off before returning), with
    the tiles from `default_blk_b` / `default_blk_n`;
  * the wsum weight fold (pinning rule 1, stages.py), the query-side idf
    gather, and the rrf rank fusion after the scan.

Its jitted program is `_run`: every launch compiles as ``jit__run``.
"""
from __future__ import annotations

from collections import OrderedDict
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.arena_scan.kernel import arena_scan_pallas
from repro.kernels.arena_scan.ref import arena_scan_scan_ref, qidf_of, rrf_fuse
from repro.kernels.arena_scan.stages import B_LANES, NEG_INF, ScanSpec

#: jnp streaming-scan tile: big enough that tile overhead (local top-k,
#: scan step) amortizes, small enough that a tile's scores stay cache-close.
BLK_SCAN = 32768

#: Candidate-set tile, in both engines: a probe's candidates are a few
#: clusters' members, which a 512-row (or pow2 scan) tile would pad with
#: dead rows.
CAND_BLK = 256


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


def default_blk_n(n: int, use_kernel: bool, page_rows: int | None = None,
                  spec: ScanSpec = ScanSpec()) -> int:
    """Tile-size policy: an explicit ``page_rows`` (the planner's
    paged-regime knob) is the tile; a candidate set tiles at `CAND_BLK`;
    the kernel's VMEM tile is 512 rows; the jnp scan uses `BLK_SCAN`
    clamped to the pow2 arena bucket so small stores stay single-tile."""
    if page_rows is not None:
        return page_rows
    if spec.slot_lane:
        return CAND_BLK
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
    work per (query row, arena row), which a wider block does not share. A
    candidate-set scan keeps `B_LANES` too: it streams a few clusters'
    rows, not the arena."""
    if spec.has_lex or spec.slot_lane:
        return B_LANES
    return min(-(-max(b, 1) // B_LANES) * B_LANES, MAX_BLK_B)


def _gather_candidates(emb, meta, slots):
    """A candidate set's streams: the rows at ``slots`` ((P,) arena slots,
    -1 padding) gathered from the ARENA, never from an index-side copy, so
    the predicate mask always sees the authoritative row; the slot rides as
    the 5th meta lane. Slots outside the arena (a poisoned or corrupt
    candidate source) are dead, not clamped."""
    slots = jnp.where((slots >= 0) & (slots < emb.shape[0]), slots, -1)
    safe = jnp.maximum(slots, 0)
    cand = meta[:, safe]
    cand = jnp.concatenate([jnp.where(slots >= 0, cand[0], -1)[None, :],
                            cand[1:], slots[None, :]], axis=0)
    return emb[safe], cand


@partial(jax.jit, static_argnames=("k", "spec", "w_dense", "w_lex", "rrf_c",
                                   "lists", "use_kernel", "blk_b", "blk_n",
                                   "page_rows", "interpret", "slots_of"))
def _run(q, emb, meta, gids, preds, lex, cand, k, spec, w_dense, w_lex,
         rrf_c, lists, use_kernel, blk_b, blk_n, page_rows, interpret,
         slots_of):
    """Every launch's one device program. Readers of a device trace find
    the scans by its module name, ``jit__run``: keep the name."""
    if slots_of is not None:
        emb, meta = _gather_candidates(emb, meta, slots_of(*cand))
    tile = page_rows or blk_n
    if spec.has_lex:
        terms, lexnorm, idf, qterms = lex
        qidf = qidf_of(idf, qterms)
        if spec.score == "fused":
            # wsum: fold the fusion weights into the inputs (pinning rule 1)
            q = q * jnp.float32(w_dense)
            qidf = qidf * jnp.float32(w_lex)
        emb, meta, terms, lexnorm = pad_dead_rows(emb, meta, tile, terms,
                                                  lexnorm)
        lex = (terms, lexnorm, qterms, qidf)
    else:
        emb, meta = pad_dead_rows(emb, meta, tile)
    if use_kernel:
        b = q.shape[0]
        q, emb = pad_d128(q, emb)
        q = _pad_axis0(q, blk_b, 0)
        gids = _pad_axis0(gids.reshape(-1, 1), blk_b, 0)
        if spec.has_lex:
            terms, lexnorm, qterms, qidf = lex
            lex = (terms, lexnorm, _pad_axis0(qterms, blk_b, -1),
                   _pad_axis0(qidf, blk_b, 0))
        out = arena_scan_pallas(q, emb, meta, gids, preds, k, spec=spec,
                                lex=lex, blk_b=blk_b, blk_n=blk_n,
                                page_rows=page_rows, interpret=interpret)
        out = tuple(a[:b] for a in out)
    else:
        out = arena_scan_scan_ref(q, emb, meta, gids, preds, k, tile,
                                  spec=spec, lex=lex)
    if spec.score == "both" and not lists:
        return rrf_fuse(*out, k, rrf_c)
    return out


def arena_scan(q, emb, tenant, updated_at, category, acl, gids, preds,
               k: int, *, spec: ScanSpec = ScanSpec(), terms=None,
               lexnorm=None, idf=None, qterms=None, w_dense: float = 1.0,
               w_lex: float = 1.0, rrf_c: float = 60.0, lists: bool = False,
               candidates: tuple | None = None,
               use_kernel: bool | None = None, blk_b: int | None = None,
               blk_n: int | None = None, page_rows: int | None = None,
               interpret: bool | None = None):
    """ONE arena scan answering every predicate group of a launch.

    q: (B, D) stacked query rows for every group; emb/tenant/updated_at/
    category/acl: the arena columns; gids: (B,) int32 group id per row;
    preds: (G, 4) int32 stacked `Predicate.as_array()` rows (the caller may
    pad them with blocker rows, tenant = -3, to bucket G); k: LIMIT.

    Lexical specs (`stages.hybrid_spec`) also take the postings lanes
    ``terms`` / ``lexnorm`` ((N, T), `LexicalArena.snapshot()`), the (V,)
    ``idf`` table and ``qterms`` ((B, QT) int32, -1 padding: a padding term
    adds exactly 0.0). wsum (``score="fused"``) ranks on w_dense*dense +
    w_lex*bm25 with the weights folded into the inputs; rrf
    (``score="both"``) keeps both per-signal k-lists and rank-fuses them
    (1/(rrf_c + rank), deduplicated union), or with ``lists=True`` returns
    them unfused (d_s, d_i, l_s, l_i) — the tiered executor merges per
    signal across tiers first.

    ``candidates=(slots_of, arrays)`` scans a candidate set (slot-lane
    specs): inside the jitted program ``slots_of(*arrays)`` gives the (P,)
    arena slots to score (-1 padding), and the results are arena slots.

    Returns (scores (B, k) f32, slots (B, k) i32, -1 past the fill).
    ``use_kernel=None`` picks the Pallas kernel on a TPU backend and the
    jnp streaming scan elsewhere; tests pass ``use_kernel=True,
    interpret=True`` to execute the kernel body on CPU. ``blk_b`` /
    ``blk_n`` default to `default_blk_b` / `default_blk_n`. ``page_rows``
    selects the paged regime: the kernel switches to HBM-resident streams
    with double-buffered DMA, the jnp scan tiles at the page size — bits
    are unchanged either way (arena_scan contract).
    """
    if lists and spec.score != "both":
        raise ValueError("lists=True is only meaningful for the rrf scan")
    use_kernel = default_use_kernel(use_kernel)
    interpret = default_interpret(interpret)
    slots_of, cand = candidates or (None, ())
    n = (emb.shape[0] if slots_of is None
         else jax.eval_shape(slots_of, *cand).shape[0])
    if blk_b is None:
        blk_b = default_blk_b(q.shape[0], spec)
    if blk_n is None:
        blk_n = default_blk_n(n, use_kernel, page_rows, spec)
    if n == 0:                      # nothing to scan: nothing qualifies
        empty = (jnp.full((q.shape[0], k), NEG_INF, jnp.float32),
                 jnp.full((q.shape[0], k), -1, jnp.int32))
        return empty * (2 if lists else 1)
    meta = _packed_meta(tenant, updated_at, category, acl)
    lex = None
    if spec.has_lex:
        lex = _packed_lanes(terms, lexnorm) + (
            jnp.asarray(idf, jnp.float32), jnp.asarray(qterms, jnp.int32))
    k_eff = min(k, n)
    out = _run(jnp.asarray(q), emb, meta, jnp.asarray(gids, jnp.int32),
               jnp.asarray(preds, jnp.int32), lex, cand, k_eff, spec,
               float(w_dense), float(w_lex), float(rrf_c), lists, use_kernel,
               blk_b, blk_n, page_rows, interpret, slots_of)
    if k_eff < k:   # LIMIT larger than the rows scanned: SQL semantics
        pad = ((0, 0), (0, k - k_eff))
        out = tuple(jnp.pad(a, pad, constant_values=NEG_INF if j % 2 == 0
                            else -1) for j, a in enumerate(out))
    return out
