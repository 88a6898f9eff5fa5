"""Per-tile stages of the unified arena scan — shared VERBATIM by the
Pallas kernel body, the jnp streaming scan, and the dense oracle.

Bit-identity between the three engines is BY CONSTRUCTION, not luck, and
this file is the construction: every engine calls the same stage functions
on the same tile values in the same order, tiling splits the arena axis N
only (never the contraction axis D), and every selection (`lax.top_k` in the
XLA engines, `merge_topk` in the kernel) breaks ties toward the lower index
locally and in every merge. Metadata and lexical lanes are LANE-MAJOR
((W, n) tiles) in every engine.

Floating-point pinning — the two rules that make the fused score
bit-stable across DIFFERENT surrounding programs (a Pallas interpret loop,
a `lax.scan`, one dense jit):

  1. **No weight multiply at the combine point.** XLA CPU contracts
     ``a*x + b*y`` into FMAs at LLVM codegen inside fused loops, and
     whether it fires depends on the surrounding fusion — the same HLO
     bits can round differently in two programs (`optimization_barrier`
     does NOT stop it: the barrier is stripped before codegen). So fusion
     weights are folded into the INPUTS (`q * w_dense` before the matmul,
     `qidf * w_lex` before the BM25 gather) and the fused score is a bare
     ``dense + bm25`` add — there is no mul+add pattern left to contract.
  2. **Guard the BM25 lane product.** The per-lane accumulation
     ``acc + w * lexnorm`` is the same contractible pattern; routing the
     product through a select (``acc + where(w != 0, w * lexnorm, 0)``)
     breaks the fmul->fadd adjacency, so LLVM emits a plain IEEE multiply
     and add in every fusion context. The select is a no-op value-wise
     (w == 0 implies w * lexnorm == 0 for the finite, non-negative lane
     weights the arena stores).
  3. **Never score a single-row matmul.** XLA CPU lowers a (1, D) x
     (D, n) contraction to a matrix-VECTOR product whose reduction order
     differs from the matrix-matrix kernel the B >= 2 shapes (and the
     Pallas body's fixed (blk_b, D) tiles) get — same inputs, different
     bits. Every jnp engine therefore pads the query block up to the
     kernel's `B_LANES` query-row lane width (zero rows, group id 0,
     sliced off after the scan), so the contraction shape — and its
     reduction order — is identical in every engine. Padding rows are
     harmless by construction: retrieval is row-parallel.

tests/test_arena_scan_conformance.py holds every engine to this contract
across shapes, page sizes, and group counts.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

NEG_INF = float(jnp.finfo(jnp.float32).min)

#: Query-row lane width every engine pads B to — pinning rule 3 above —
#: and the multiple the kernel's query-row block ``blk_b`` rounds up to
#: (`ops.default_blk_b`). The kernel streams the arena once per block: a
#: dense scan holds the whole batch (up to 128 rows) in one block; a scan
#: with a lexical stage keeps blocks of B_LANES rows.
B_LANES = 8


@dataclasses.dataclass(frozen=True)
class ScanSpec:
    """What one arena-scan program computes.

    score:
      * ``"dense"`` — similarity only (the filtered, grouped and ivf
        presets): ONE running k-list on the masked dot product;
      * ``"fused"`` — hybrid wsum: ONE running k-list on ``dense + bm25``
        (fusion weights pre-folded into q / qidf by the caller);
      * ``"both"``  — hybrid rrf: TWO running k-lists (dense, bm25) — rank
        fusion needs retrieved lists, so it happens after the scan.
    slot_lane: the metadata block carries a 5th lane with each row's ARENA
      slot (ivf candidate sets): the slot is the output index source, and
      ``slot < 0`` rows (member-table padding) are masked out.
    """
    score: str = "dense"
    slot_lane: bool = False

    @property
    def n_lists(self) -> int:
        return 2 if self.score == "both" else 1

    @property
    def has_lex(self) -> bool:
        return self.score in ("fused", "both")

    @property
    def meta_width(self) -> int:
        return 5 if self.slot_lane else 4


def hybrid_spec(mode: str) -> ScanSpec:
    """The scan a fusion mode runs: ``wsum`` one fused list, ``rrf`` the
    two per-signal lists."""
    if mode not in ("wsum", "rrf"):
        raise ValueError(f"unknown fusion mode {mode!r}")
    return ScanSpec(score="fused" if mode == "wsum" else "both")


def merge_topk(best_s, best_i, scores, idx, k: int):
    """Merge (B, M) tile candidates into the running (B, K) best lists.

    Ties break toward the lower concatenation position — running list
    first, then tile index order — which is what keeps every engine's
    winner set identical to the dense oracle's single `top_k`.

    Mosaic has no `top_k` lowering, so the selection is k rounds of: row
    max, lowest position holding it, mask that position. Every step is an
    exact compare/select (no arithmetic on scores), so the result is the
    same (value, index) sequence `lax.top_k` returns, in descending order.

    The kernel calls it only for a tile that can change the lists (its
    merge gate, `kernel` module docstring): where no tile score is above
    its row's k-th entry ``best_s[:, k-1]``, every round picks the list's
    own entry, which comes first on a tie, so the merge returns
    ``(best_s, best_i)`` unchanged and skipping it is exact."""
    all_s = jnp.concatenate([best_s, scores], axis=1)
    all_i = jnp.concatenate([best_i, idx], axis=1)
    b, m = all_s.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (b, m), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)

    def round_(r, carry):
        cand, new_s, new_i = carry
        top = jnp.max(cand, axis=1, keepdims=True)                  # (B, 1)
        at = jnp.min(jnp.where(cand == top, pos, m), axis=1, keepdims=True)
        hit = pos == at
        top_i = jnp.max(jnp.where(hit, all_i, jnp.iinfo(jnp.int32).min),
                        axis=1, keepdims=True)
        new_s = jnp.where(col == r, top, new_s)
        new_i = jnp.where(col == r, top_i, new_i)
        return jnp.where(hit, -jnp.inf, cand), new_s, new_i

    init = (all_s, jnp.full((b, k), NEG_INF, jnp.float32),
            jnp.full((b, k), -1, jnp.int32))
    _, new_s, new_i = jax.lax.fori_loop(0, k, round_, init)
    return new_s, new_i


def dense_scores(q, e):
    """Similarity stage (MXU): (B, D) x (n, D) -> (B, n) f32 dot product.
    The contraction axis D is never tiled, so every engine computes the
    same per-element reduction. Precision is pinned to exact f32: on a TPU
    the default f32 matmul rounds its inputs to bf16, and XLA and Mosaic
    could then rank the same rows differently (on CPU this changes no
    bits)."""
    return jax.lax.dot_general(q.astype(jnp.float32), e.astype(jnp.float32),
                               (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def bm25_scores(terms, lexnorm, qterms, qidf):
    """Lexical stage (VPU): masked-gather BM25 over one tile's postings
    lanes, LANE-MAJOR. terms: (T, n) int32 lane term ids (-1 empty);
    lexnorm: (T, n) f32 per-lane tf/length weight; qterms: (B, QT) int32
    (-1 padding); qidf: (B, QT) f32 per-term idf (0 on padding, fusion
    weight already folded in). Returns (B, n) f32.

    The accumulation order is FIXED — lanes outer, query terms inner — and
    the lane product is select-guarded (see module docstring, rule 2), so
    the sum is the same IEEE value in every fusion context. Lanes are
    static (1, n) row slices of the lane-major tile — a sublane pick, where
    a column slice of an (n, T) tile would be a relayout per lane.
    Padding safety: a padding query term (-1) can only "match" an empty
    doc lane (-1), and its gathered idf is 0, so it contributes exactly
    0.0. The T x QT loop stays unrolled: a `fori_loop` over lanes read
    from the kernel's VMEM refs compiles faster but took twice the device
    time of a pass on a v5e."""
    bm25 = jnp.zeros((qterms.shape[0], terms.shape[1]), jnp.float32)
    for t in range(terms.shape[0]):
        lane = terms[t:t + 1, :]                                   # (1, n)
        w = jnp.zeros_like(bm25)
        for j in range(qterms.shape[1]):
            w = w + jnp.where(lane == qterms[:, j:j + 1], qidf[:, j:j + 1], 0.0)
        bm25 = bm25 + jnp.where(w != 0.0, w * lexnorm[t:t + 1, :], 0.0)
    return bm25


def predicate_keep(meta, preds):
    """Mask stage: all G engine-level WHERE clauses over one metadata tile,
    one broadcast pass. meta: (>=4, n) int32, LANE-MAJOR rows [tenant,
    updated_at, category, acl, ...] (a page of it is an aligned (M, page)
    slice); preds: (G, 4) int32 stacked `Predicate.as_array()` rows.
    Returns (G, n) bool — row is live AND satisfies group g's clauses."""
    tenant = meta[0:1, :]
    ts = meta[1:2, :]
    cat = meta[2:3, :]
    acl = meta[3:4, :]
    p_tenant = preds[:, 0:1]
    p_ts = preds[:, 1:2]
    p_cat = preds[:, 2:3]
    p_acl = preds[:, 3:4]
    keep = tenant >= 0                                     # live rows only
    keep &= (p_tenant == -2) | (tenant == p_tenant)        # tenant isolation
    keep &= ts >= p_ts                                     # freshness
    keep &= (jnp.left_shift(1, cat) & p_cat) != 0          # category set
    keep &= (acl & p_acl) != 0                             # ACL groups
    return keep


def row_keep_onehot(keep, gids):
    """Group select, kernel form: each query row picks ITS group's mask by
    one-hot matmul (Mosaic-safe — no dynamic gather inside the kernel).
    keep: (G, n) bool; gids: (B, 1) int32. Returns (B, n) bool, boolean-
    identical to ``keep[gids[:, 0]]``: the matmul operands are exact 0/1
    floats, so the > 0 threshold recovers the same booleans."""
    n_groups = keep.shape[0]
    onehot = (gids == jax.lax.broadcasted_iota(
        jnp.int32, (1, n_groups), 1)).astype(jnp.float32)  # (B, G)
    return jax.lax.dot_general(
        onehot, keep.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) > 0.0          # (B, n)


def tile_mask(spec: ScanSpec, meta, preds, gids, *, onehot: bool):
    """Full mask stage for one tile: predicate groups -> per-row select
    (+ slot-lane membership for candidate-set scans). gids is (B, 1) when
    ``onehot`` (kernel form) else (B,) (ref gather form) — the two forms
    are boolean-identical."""
    keep = predicate_keep(meta, preds)
    row_keep = row_keep_onehot(keep, gids) if onehot else keep[gids]
    if spec.slot_lane:
        row_keep &= meta[4:5, :] >= 0                      # member padding out
    return row_keep


def tile_signals(spec: ScanSpec, q, e, row_keep, lex=None, *,
                 barrier: bool = False):
    """Score stage for one tile: the masked running-list signals, one per
    `spec.n_lists`. ``lex`` is (terms, lexnorm, qterms, qidf) when
    `spec.has_lex`. ``barrier`` sequences the elementwise BM25 chain before
    the threaded dense matmul (scheduling only — the jit'd refs measure
    ~1.5x faster with it, values are untouched; the Pallas body skips it)."""
    if spec.has_lex:
        terms, lexnorm, qterms, qidf = lex
        bm25 = bm25_scores(terms, lexnorm, qterms, qidf)
        if barrier:
            bm25 = jax.lax.optimization_barrier(bm25)
    dense = dense_scores(q, e)
    if spec.score == "dense":
        return (jnp.where(row_keep, dense, NEG_INF),)
    if spec.score == "fused":
        # weights are pre-folded into q / qidf: a bare add has no mul+add
        # pattern for LLVM to contract (see module docstring, rule 1)
        return (jnp.where(row_keep, dense + bm25, NEG_INF),)
    return (jnp.where(row_keep, dense, NEG_INF),
            jnp.where(row_keep, bm25, NEG_INF))
