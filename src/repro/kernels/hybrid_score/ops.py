"""The arena scan's jitted program under its former module path.

The scan lives in `repro.kernels.arena_scan.ops`. This module only
re-exports its `_run`, because the benchmark harness's own tests count the
programs a hybrid run compiles through this path
(`bench/tests/test_hybrid_cell.py`); it goes once they read
`repro.kernels.arena_scan.ops._run` instead.
"""
from repro.kernels.arena_scan.ops import _run  # noqa: F401
