"""The keyword-heavy hybrid cell (`hybrid768.msmarco`) on CPU: its
readers on a traced run, its scan's bytes at 64 lanes, the programs its
warm-up compiles, and the tests' own keyword mix left in place."""
import json
import os
from collections import Counter

import numpy as np
import pytest

from bench import harness
from bench.metrics import _program_trace, reader
from bench.metrics._program_trace import Event
from bench.metrics._scan_cost import least_seconds, pass_bytes
from bench.trace import Op, Profile

from repro.api.executor import CompiledShapes, _pad_group_launch
from repro.api.plan import bucket_rows
from repro.core.query import Predicate

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(BENCH, "configs", "hybrid768.json")


def _config():
    with open(CONFIG) as f:
        return json.load(f)


def test_pass_bytes_at_64_lanes():
    """A hybrid pass at 2^20 rows reads 3,600 B a row: 768 f32, four
    int32 metadata lanes, and 64 term-id and 64 weight lanes."""
    cfg = _config()
    run = harness.RunRecord(
        config=cfg, peaks={"hbm_bytes_per_s": 819e9,
                           "bf16_flops_per_s": 197e12},
        max_batch=16, plan_ms=[], queue_wait_ms=[], spans=[], profile=None,
        engines=Counter(["hybrid"]), writes_in_window=0)
    assert pass_bytes(run, hybrid=True) == (1 << 20) * 3600
    least, bound = least_seconds(run, hybrid=True)
    assert bound == "hbm"
    assert least == pytest.approx((1 << 20) * 3600 / 819e9)


def test_warm_up_compiles_at_most_60_programs():
    """The warm-up's launch shapes (every (rows, groups) signature of a
    16-read batch, times each fuse key) reach one hybrid program per row
    bucket, fusion mode and query-term bucket: the executor pads a hybrid
    launch's groups to its row bucket."""
    cfg = _config()
    max_batch = int(cfg["serving"]["max_batch"])
    sigs = harness.shape_signatures(max_batch, 1)
    qts = {bucket_rows(n) for n in range(1, cfg["lexical"]
                                         ["max_query_terms"] + 1)}
    programs = set()
    for n, g in sigs.values():
        q, gids, preds, _ = _pad_group_launch(
            np.zeros((n, 8), np.float32), np.arange(n, dtype=np.int32) % g,
            [Predicate(tenant=t) for t in range(g)], 10, "hybrid",
            stats=None, shapes=CompiledShapes(), groups_per_row=True)
        programs.add((q.shape[0], len(preds)))
    n_programs = len(programs) * 2 * len(qts)
    assert len(sigs) * 2 * len(qts) == 150       # warm batches
    assert n_programs == 50 <= 60


def test_tests_keyword_mix_still_loads(tiny):
    """`bench/traffic/` holds no `keyword.json`: the harness tests' own mix
    is the one `tiny("hybrid.keyword")` loads."""
    assert not os.path.exists(os.path.join(BENCH, "traffic", "keyword.json"))
    with open(os.path.join(BENCH, "tests", "data", "keyword.json")) as f:
        own = json.load(f)
    assert tiny("hybrid.keyword").mix["queries"] == own["queries"]


def test_readers_on_a_traced_run(tiny):
    """A traced CPU run of the cell: its warm-up compiles one hybrid
    program per row bucket, mode and term bucket; the span readers read the
    launches' term fill and scans per batch; the run is correct."""
    from repro.kernels.hybrid_score import ops
    cell = tiny("hybrid768.msmarco")
    compiled = ops._run._cache_size()
    line = harness.run(cell, 2**32 + 5, 1.0, True, t_proc0=0.0,
                       say=lambda *_: None,
                       peaks=harness.peaks_of("TPU v5 lite"))
    # batches of at most 8: 4 row buckets x 2 modes x 5 term buckets
    assert ops._run._cache_size() - compiled <= 40
    m = line["metrics"]
    assert line["correct"] is True, line["checks"]
    assert 0 < m["lex_term_fill"]["value"] <= 100
    assert m["scans_per_batch"]["value"] >= 1
    for name in ("plan_ms", "queue_wait_ms", "device_sync_ms",
                 "device_idle_pct", "plan_compile_ms", "pipeline_hold_ms"):
        assert m[name]["value"] > 0, name


def _hybrid_launch(unit, t0, rows, bucket, qt, qterms, batch):
    return Event("launch", t0, t0 + 0.001, {
        "unit": unit, "rows": rows, "bucket": bucket, "family": "hybrid",
        "passes": -(-bucket // 8), "block_rows": 8, "mode": "wsum",
        "qt": qt, "qterms": qterms, "lanes": 64, "batch": batch})


def _recorded(monkeypatch, events, ops, engine):
    monkeypatch.setattr(_program_trace, "_load", lambda run: list(events))
    return harness.RunRecord(
        config=_config(), peaks={"hbm_bytes_per_s": 819e9,
                                 "bf16_flops_per_s": 197e12},
        max_batch=16, plan_ms=[0.1], queue_wait_ms=[1.0], spans=[],
        profile=Profile(ops=ops, notes=[("bench.window", 0.0, 1.0)],
                        n_devices=1),
        engines=Counter([engine]), writes_in_window=0)


def test_device_readers_on_a_recorded_trace(monkeypatch):
    """Two hybrid launches (5 rows in one pass, 11 rows in two) and their
    scans of 20 and 40 ms: 20 ms a pass, and the roofline counts each
    launch's op as one pass's least time. On a dense run the hybrid
    readers find nothing."""
    events = [_hybrid_launch(1, 0.100, 5, 8, 4, 17, 1),
              _hybrid_launch(2, 0.102, 11, 16, 8, 60, 1)]
    ops = [Op("_run.1", "jit__run", 0.110, 0.020, kernel=True),
           Op("_run.1", "jit__run", 0.131, 0.040, kernel=True)]
    run = _recorded(monkeypatch, events, ops, "hybrid")
    assert reader("hybrid_pass_ms")(run) == pytest.approx(60.0 / 3)
    least, _ = least_seconds(run, hybrid=True)
    assert reader("arena_scan_roofline.hybrid")(run) == pytest.approx(
        100 * 2 * least / 0.060)
    dense = [Event("launch", 0.1, 0.101,
                   {"family": "grouped", "passes": 1, "rows": 3})]
    run = _recorded(monkeypatch, dense, ops[:1], "pallas")
    assert reader("arena_scan_roofline.hybrid")(run) is None
    assert reader("hybrid_pass_ms")(run) is None


class _Span:
    def __init__(self, name, **ann):
        self.name, self.t0, self.t1, self.ann = name, 0.0, 1.0, ann


class _Trace:
    def __init__(self, spans):
        self.spans = spans


def test_span_readers_on_recorded_spans():
    """Three hybrid launches in two batches (5 x 4 and 11 x 8 term slots,
    then 2 x 1): fill = (17 + 60 + 2) / (20 + 88 + 2); 1.5 scans a batch.
    A dense launch and a launch from a program without the new fields
    count for neither."""
    spans = [_Span("launch", unit=1, family="hybrid", rows=5, qt=4,
                   qterms=17, batch=1),
             _Span("launch", unit=2, family="hybrid", rows=11, qt=8,
                   qterms=60, batch=1),
             _Span("launch", unit=3, family="hybrid", rows=2, qt=1,
                   qterms=2, batch=2),
             _Span("launch", unit=4, family="grouped", rows=3, batch=2),
             _Span("launch", unit=5, family="hybrid", rows=4)]
    run = harness.RunRecord(
        config=_config(), peaks={}, max_batch=16, plan_ms=[],
        queue_wait_ms=[], spans=[_Trace(spans)], profile=None,
        engines=Counter(["hybrid"]), writes_in_window=0)
    assert reader("lex_term_fill")(run) == pytest.approx(
        100 * 79 / 110)
    assert reader("scans_per_batch")(run) == pytest.approx(1.5)
    run.spans = [_Trace(spans[3:])]
    assert reader("lex_term_fill")(run) is None
    assert reader("scans_per_batch")(run) is None
