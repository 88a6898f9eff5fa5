"""The unified paged arena-scan framework.

Every retrieval engine in the repo is the SAME program: stream the arena in
tiles, score each tile (dense dot / BM25 / fused hybrid), mask it (predicate
groups via one-hot matmul, slot-lane membership, blocker lanes), and keep a
running top-k in VMEM scratch. This package owns that program once:

  * `stages`  — the per-tile math, shared VERBATIM by the Pallas kernel
    body, the jnp streaming scan, and the dense oracle (the structural
    bit-identity guarantee);
  * `kernel`  — the Pallas kernel, in two regimes: resident (BlockSpec
    grid pipelining, arena fits VMEM streaming) and paged (HBM-resident
    arena, explicit double-buffered DMA so the next page's copy overlaps
    the current page's compute);
  * `ref`     — the dense oracle and the streaming jnp scan, generic over
    the same `ScanSpec` (the kernel-level oracles of every spec), and the
    lexical helpers every scorer shares (`qidf_of`, `bm25_block`,
    `rrf_fuse`);
  * `ops`     — `arena_scan`, the one public entry: padding, metadata
    packing, engine choice, tiles, weight folding and rank fusion, once;
  * `sharded` — the shard-mapped scan of the `sharded` engine.

The scan families are `ScanSpec` presets of the entry, not packages:
filtered (dense, G = 1), grouped (dense, G >= 1), hybrid
(`stages.hybrid_spec`) and ivf (`ScanSpec(slot_lane=True)` over a
candidate set).
"""
from repro.kernels.arena_scan.stages import NEG_INF, ScanSpec, merge_topk

__all__ = ["NEG_INF", "ScanSpec", "merge_topk"]
