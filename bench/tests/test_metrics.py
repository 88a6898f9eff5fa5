"""Each per-layer reader on a small recorded trace."""
from collections import Counter

import pytest

from bench import harness
from bench.metrics import reader
from bench.metrics._scan_cost import least_seconds, pass_bytes
from bench.trace import Op, Profile

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CONFIG = {"store": {"capacity": 2097152, "dim": 768, "dtype": "float32"},
          "lexical": {"doc_terms": 16}}


class _Span:
    def __init__(self, name, t0, t1):
        self.name, self.t0, self.t1 = name, t0, t1


class _Trace:
    def __init__(self, spans):
        self.spans = spans


def _record(ops, notes, *, engines=("pallas",), writes=0, spans=()):
    profile = Profile(ops=ops, notes=notes, n_devices=1)
    return harness.RunRecord(
        config=CONFIG, peaks=PEAKS, max_batch=16, plan_ms=[0.2, 0.4, 0.3],
        queue_wait_ms=list(range(1, 101)), spans=list(spans),
        profile=profile, engines=Counter(engines), writes_in_window=writes)


def _recorded():
    """A 100 ms window: two scans of 10 ms, a commit of 20 ms, 5 ms of small
    ops, and one op outside the window."""
    ops = [Op("_run.1", "jit__run", 0.010, 0.010, kernel=True),
           Op("fusion.3", "jit__run", 0.020, 0.001),
           Op("_run.1", "jit__run", 0.040, 0.010, kernel=True),
           Op("copy.2", "jit_update", 0.060, 0.020),
           Op("fusion", "jit_stack", 0.085, 0.004),
           Op("_run.1", "jit__run", 0.200, 0.010, kernel=True)]
    notes = [("bench.window", 0.0, 0.1), ("bench.step", 0.0, 0.05),
             ("bench.write", 0.055, 0.081), ("bench.idle", 0.09, 0.1)]
    return ops, notes


def test_scan_cost_is_hbm_bound_at_768():
    run = _record([], [])
    assert pass_bytes(run, hybrid=False) == 2097152 * (768 * 4 + 16)
    assert pass_bytes(run, hybrid=True) == 2097152 * (768 * 4 + 16 + 128)
    least, bound = least_seconds(run, hybrid=False)
    assert bound == "hbm"
    assert least == pytest.approx(2097152 * 3088 / 819e9)


def test_device_trace_readers():
    ops, notes = _recorded()
    run = _record(ops, notes, writes=1)
    # busy: [10,21] + [40,50] + [60,80] + [85,89] ms = 45 ms of 100
    assert reader("device_idle_pct")(run) == pytest.approx(55.0)
    assert reader("commit_ms")(run) == pytest.approx(20.0)
    least, _ = least_seconds(run, hybrid=False)
    assert reader("arena_scan_roofline.dense")(run) == pytest.approx(
        100 * 2 * least / 0.020)
    gaps = run.profile.idle_gaps()
    # gaps [0,10] [21,40] ms under the step, [50,60] [80,85] most under
    # the write, [89,100] under the idle wait
    assert gaps["bench.step"] == pytest.approx(0.010 + 0.019)
    assert gaps["bench.write"] == pytest.approx(0.010 + 0.005)
    assert gaps["bench.idle"] == pytest.approx(0.011)


def test_readers_find_nothing_and_say_so():
    run = _record([], [], writes=0)
    run.profile = None
    for name in ("device_idle_pct", "commit_ms", "arena_scan_roofline.dense",
                 "device_sync_ms"):
        assert reader(name)(run) is None


def test_host_and_span_readers():
    spans = [_Trace([_Span("device_sync", 1.0, 1.004),
                     _Span("merge", 1.004, 1.005)]),
             _Trace([_Span("device_sync", 1.0, 1.004)]),
             _Trace([_Span("device_sync", 2.0, 2.002)])]
    run = _record([], [], spans=spans)
    assert reader("plan_ms")(run) == pytest.approx(0.3)
    assert reader("queue_wait_ms")(run) == pytest.approx(95.05)
    # one dispatch unit fans its span into two traces: counted once
    assert reader("device_sync_ms")(run) == pytest.approx(3.0)


def test_live_trace_of_a_traced_run(tiny):
    """A traced CPU run: the trace reads back with a window, busy time and
    the commit program; the result line carries the per-layer metrics."""
    cell = tiny("dense768.ycsb-b")
    line = harness.run(cell, 3, 1.0, True, t_proc0=0.0,
                       say=lambda *_: None,
                       peaks=harness.peaks_of("TPU v5 lite"))
    m = line["metrics"]
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    for name in ("plan_ms", "queue_wait_ms", "device_sync_ms",
                 "device_idle_pct", "commit_ms"):
        assert m[name]["value"] > 0, name
    assert line["breakdown"]["device_ops"]
    assert line["correct"] is True
