"""The readers of the program's own spans and counters, on synthetic
records: the ``rag.*`` profiler events stand in for a parsed trace."""
from collections import Counter

import pytest

from bench import harness
from bench.metrics import _program_trace, reader
from bench.metrics._program_trace import Event
from bench.metrics._scan_cost import least_seconds
from bench.trace import Op, Profile

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CONFIG = {"store": {"capacity": 2097152, "dim": 768, "dtype": "float32"}}
NEW = ("plan_compile_ms", "pipeline_hold_ms", "device_queue_ms",
       "scan_pass_ms", "scan_rows_per_pass")


class _Span:
    def __init__(self, name, t0, t1, **ann):
        self.name, self.t0, self.t1, self.ann = name, t0, t1, ann


class _Trace:
    def __init__(self, spans):
        self.spans = spans


def _launch(unit, t0, t1, rows, bucket, family="grouped"):
    stats = {"unit": unit, "rows": rows, "bucket": bucket, "family": family}
    if family != "ref":
        stats["passes"] = -(-bucket // 8)
    return Event("launch", t0, t1, stats)


def _record(monkeypatch, events, ops, spans=(), window=(0.0, 1.0)):
    """A run whose profile holds ``ops`` in ``window`` and whose trace
    holds ``events``."""
    monkeypatch.setattr(_program_trace, "_load", lambda run: list(events))
    profile = Profile(ops=ops, notes=[("bench.window", *window)],
                      n_devices=1)
    return harness.RunRecord(
        config=CONFIG, peaks=PEAKS, max_batch=16, plan_ms=[0.2],
        queue_wait_ms=[1.0], spans=list(spans), profile=profile,
        engines=Counter(["pallas"]), writes_in_window=0)


def _scans():
    """Two launches (11 rows in a 16-row bucket, then 5 in 8) and their
    scans: 48 ms and 25 ms on the chip, each starting a while after its
    launch ended; a third launch of the ref engine, which has no scan op."""
    events = [_launch(1, 0.100, 0.101, rows=11, bucket=16),
              Event("device_sync", 0.102, 0.150, {"unit": 1}),
              _launch(2, 0.105, 0.106, rows=5, bucket=8),
              _launch(3, 0.300, 0.301, rows=2, bucket=2, family="ref"),
              Event("launch", 1.5, 1.6, {"family": "grouped", "passes": 1})]
    ops = [Op("_run.1", "jit__run", 0.111, 0.048, kernel=True),
           Op("fusion", "jit__run", 0.160, 0.001),
           Op("_run.1", "jit__run", 0.166, 0.025, kernel=True)]
    return events, ops


def test_device_queue_and_pass_time(monkeypatch):
    events, ops = _scans()
    run = _record(monkeypatch, events, ops)
    # launch 1 ends at 101 ms, its scan starts at 111; launch 2: 106 -> 166
    assert reader("device_queue_ms")(run) == pytest.approx((10 + 60) / 2)
    # 73 ms of scans over 2 + 1 passes
    assert reader("scan_pass_ms")(run) == pytest.approx(73.0 / 3)
    # the roofline reader divides the same device time by the launches:
    # roofline = 100 x least / (pass time x mean passes per launch)
    least, _ = least_seconds(run, hybrid=False)
    roof = reader("arena_scan_roofline.dense")(run)
    assert roof == pytest.approx(
        100 * least / (reader("scan_pass_ms")(run) / 1e3 * 3 / 2))


def test_launches_and_ops_that_do_not_pair(monkeypatch):
    events, ops = _scans()
    run = _record(monkeypatch, events, ops[:1])
    assert reader("device_queue_ms")(run) is None
    assert reader("scan_pass_ms")(run) is None
    # a launch with no passes to count (an older program) pairs but is
    # not a pass time
    bare = [Event("launch", 0.1, 0.101, {"unit": 1, "family": "grouped"})]
    run = _record(monkeypatch, bare, ops[:1])
    assert reader("device_queue_ms")(run) == pytest.approx(10.0)
    assert reader("scan_pass_ms")(run) is None


def test_span_readers(monkeypatch):
    spans = [
        _Trace([_Span("plan_compile", 0.0, 0.00004),
                _Span("launch", 0.1, 0.101, unit=1, rows=11, bucket=16,
                      passes=2, family="grouped"),
                _Span("pending", 0.101, 0.141, unit=1)]),
        _Trace([_Span("plan_compile", 0.01, 0.01005),
                _Span("launch", 0.1, 0.101, unit=1, rows=11, bucket=16,
                      passes=2, family="grouped"),
                _Span("pending", 0.101, 0.141, unit=1)]),
        _Trace([_Span("plan_compile", 0.02, 0.02009),
                _Span("launch", 0.2, 0.201, unit=2, rows=5, bucket=8,
                      passes=1, family="grouped"),
                _Span("pending", 0.201, 0.221, unit=2),
                _Span("launch", 0.3, 0.301, unit=3, rows=2, bucket=2,
                      family="ref")])]
    run = _record(monkeypatch, [], [], spans=spans)
    assert reader("plan_compile_ms")(run) == pytest.approx(0.05)
    # one interval per batch, fanned into two traces: counted once
    assert reader("pipeline_hold_ms")(run) == pytest.approx((40 + 20) / 2)
    # 16 real rows over 3 passes; the ref launch has no passes
    assert reader("scan_rows_per_pass")(run) == pytest.approx(16 / 3)


def test_readers_find_nothing_and_say_so(monkeypatch):
    # a program that writes neither the new spans nor rag.* events
    spans = [_Trace([_Span("launch", 0.1, 0.101, rows=3),
                     _Span("device_sync", 0.2, 0.25)])]
    events, ops = _scans()
    run = _record(monkeypatch, [], ops, spans=spans)
    for name in NEW:
        assert reader(name)(run) is None, name
    run.profile = None                      # an untraced run
    for name in NEW:
        assert reader(name)(run) is None, name


def test_events_are_parsed_once_per_run(monkeypatch):
    events, ops = _scans()
    calls = []
    run = _record(monkeypatch, events, ops)
    monkeypatch.setattr(_program_trace, "_load",
                        lambda r: calls.append(r) or list(events))
    for name in NEW:
        reader(name)(run)
    assert len(calls) == 1
    # the window bounds the events: the launch at 1.5 s lies outside
    assert [e.stats.get("unit") for e in _program_trace.scan_launches(run)] \
        == [1, 2]
