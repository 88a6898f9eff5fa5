# Pallas TPU kernels. `arena_scan/` is the one retrieval kernel: every
# scan family (filtered, grouped, hybrid, ivf) is a `ScanSpec` preset of
# its entry `arena_scan.ops.arena_scan`. `hybrid_score/ops.py` only
# re-exports that entry's `_run` under its former path.
