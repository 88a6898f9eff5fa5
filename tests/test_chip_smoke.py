"""CPU rehearsal of `chip_smoke.py`'s one-chip body at a tiny size.

The script itself refuses to run without a TPU. Here its body runs at
4,096 rows x D=128 with every Pallas kernel in interpret mode: the test
steers the TPU-only choices — the planner's backend probe and its row
threshold (so plans pick the `pallas` engine) and the arena scan's
kernel default — and the body's own checks do the rest (zero isolation violations, zero untied
oracle mismatches, every scheduled request fresh or cached, every write
read back).
"""
import functools
import importlib.util
import os
import sys
import types

import pytest

from repro.api import planner
from repro.api.planner import PlannerConfig
from repro.kernels.arena_scan import ops as scan_ops

pytestmark = pytest.mark.kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_body_tiny(monkeypatch):
    smoke = _load_smoke()
    monkeypatch.setattr(planner, "jax",
                        types.SimpleNamespace(default_backend=lambda: "tpu"))
    monkeypatch.setattr(scan_ops, "default_use_kernel",
                        lambda use_kernel: True if use_kernel is None
                        else use_kernel)
    # the arena is far below the planner's pallas threshold at this size
    monkeypatch.setattr(smoke, "RagDB", functools.partial(
        smoke.RagDB, planner_cfg=PlannerConfig(pallas_min_rows=1)))
    cfg = smoke.SmokeConfig(capacity=4096, dim=128, n_docs=3000,
                            chunk_rows=1000, n_new=256, n_update=128,
                            n_delete=128)
    report = smoke.run_one_chip(cfg)
    assert report["violations"] == 0
    assert report["mismatches"] == 0
    assert report["rows_checked"] > 400
    assert set(report["sched_served"]) <= {"fresh", "cache"}
    assert report["sched_fused_scans"] >= 1
    assert not report["readback_failures"]
    assert all(n > 0 for n in report["readback"].values())
