"""The reader of the arena-scan kernel's merge tally, on synthetic spans:
``tiles_merged`` of ``tiles`` on each unit's ``device_sync`` span."""
from collections import Counter

import pytest

from bench import harness
from bench.metrics import reader


class _Span:
    def __init__(self, name, t0, t1, **ann):
        self.name, self.t0, self.t1, self.ann = name, t0, t1, ann


class _Trace:
    def __init__(self, spans):
        self.spans = spans


def _run(spans):
    return harness.RunRecord(
        config={}, peaks={}, max_batch=16, plan_ms=[0.2], queue_wait_ms=[1.0],
        spans=spans, profile=None, engines=Counter(["pallas"]),
        writes_in_window=0)


def test_share_of_tiles_merged():
    # unit 1 (two lists of 2048 tiles) fans into two traces: counted once;
    # the ref unit carries no tally
    sync1 = dict(unit=1, tiles_merged=300, tiles=4096)
    spans = [_Trace([_Span("device_sync", 0.1, 0.2, **sync1)]),
             _Trace([_Span("device_sync", 0.1, 0.2, **sync1),
                     _Span("device_sync", 0.3, 0.4, unit=2, tiles_merged=100,
                           tiles=4096)]),
             _Trace([_Span("device_sync", 0.5, 0.6, unit=3)])]
    assert reader("scan_merge_share")(_run(spans)) == pytest.approx(
        100 * 400 / 8192)


def test_no_tally_reads_none():
    # a program whose kernel merges every tile writes no tally
    spans = [_Trace([_Span("launch", 0.1, 0.101, rows=3),
                     _Span("device_sync", 0.2, 0.25, unit=1)])]
    assert reader("scan_merge_share")(_run(spans)) is None
    assert reader("scan_merge_share")(_run([])) is None
