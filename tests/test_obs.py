"""Observability layer: span trees, flight recorder, calibration audit.

The contracts under test (ISSUE 10):

  * COMPLETENESS — with the tracer on, every executed request yields a
    finished trace whose span tree is well-formed (root ``request``, valid
    parent links, closed monotone intervals nested inside the root) and
    covers the pipeline stages the request actually crossed
    (cache_lookup -> launch -> device_sync -> merge, plan_compile/queue/
    degrade/pending under the scheduler).
  * ZERO-COST DISABLED — tracer off is the default and results are
    bit-identical to tracer on: tracing observes, never steers.
  * PROFILER CLOCK — under an active `jax.profiler` session an enabled
    tracer writes each span as a ``rag.<name>`` host event (one per fan
    span, with ``unit`` / ``req_id`` stats); a disabled tracer writes none;
    each launch span carries the shape it launched (rows, bucket, passes).
  * PINNING — the flight recorder's ring is bounded, pinned (slo /
    degraded / fault / failed) traces survive the ring rolling past them,
    the pin list is bounded too (drops counted), and fault/degradation
    pins are applied automatically on the serving path.
  * EXPORT — the Perfetto ``trace_event`` conversion is JSON-round-trip
    stable and `tools/trace_report.py` rebuilds the identical event list
    from a dump file.
  * CALIBRATION — predicted-vs-measured recording is always on (tracer
    independent), keyed by (engine, N-bucket, G, k), and
    `CostModel.calibrated` rescales curves by the measured drift.
"""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RagDB
from repro.api.planner import CostModel, PlannerConfig
from repro.core import StoreConfig
from repro.data.corpus import DAY_S, CorpusConfig, make_corpus
from repro.index.lexical import LexicalConfig
from repro.obs import CalibrationTable, FanSpan, FlightRecorder, Tracer
from repro.obs.calibration import pow2_bucket
from repro.serving.faults import FaultPlan, FaultRule
from repro.serving.metrics import MetricsRegistry
from repro.serving.scheduler import Scheduler, SchedulerConfig, ServeRequest
from tests.test_scheduler import FakeClock

ALL_BITS = 0xFFFFFFFF


# -- helpers ---------------------------------------------------------------

def _db(n_docs=300, dim=16, tiered=False, measured=False):
    ccfg = CorpusConfig(n_docs=n_docs, dim=dim, n_tenants=3, n_categories=4)
    scfg = StoreConfig(capacity=512, dim=dim)
    kw = {}
    if tiered:
        kw = dict(warm_cfg=scfg, hot_window_s=90 * DAY_S)
    if measured:
        kw["planner_cfg"] = PlannerConfig.with_measured_costs()
    db = RagDB(scfg, now_ts=ccfg.now_ts, **kw)
    db.ingest(make_corpus(ccfg))
    if tiered:
        assert db.router.warm.n_docs > 0
    return db, ccfg


def _plans(db, ccfg, n, seed=0, k=6):
    rng = np.random.default_rng(seed)
    sess = db.admin_session()
    return [sess.search(rng.standard_normal(ccfg.dim).astype(np.float32),
                        normalize=False).limit(k).plan() for _ in range(n)]


def _assert_well_formed(trace):
    """Structural span-tree invariants: closed, monotone, parent-linked,
    nested inside the root interval."""
    assert trace.finished
    spans = trace.spans
    root = spans[0]
    assert root.name == "request" and root.parent_id == -1
    ids = {s.span_id for s in spans}
    assert len(ids) == len(spans)           # unique ids
    for s in spans:
        assert s.t1 is not None, f"span {s.name} left open"
        assert s.t1 >= s.t0
        if s is not root:
            assert s.parent_id in ids       # valid parent link
            # batch-shared fans are stamped with one shared clock pair, so
            # every child interval nests inside the root's
            assert root.t0 <= s.t0 and s.t1 <= root.t1 + 1e-9


# -- span-tree completeness ------------------------------------------------

def test_execute_trace_covers_pipeline_stages():
    db, ccfg = _db()
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    plans = _plans(db, ccfg, 4)
    db.execute(plans)                       # cache on: misses, full pipeline
    got = rec.traces()
    assert len(got) == len(plans)
    for t in got:
        _assert_well_formed(t)
        names = [s.name for s in t.spans]
        for stage in ("request", "cache_lookup", "launch", "device_sync",
                      "merge"):
            assert stage in names, (stage, names)
        assert t.root.ann["served"] in ("fresh", "cache", "stale")
    # no cache consulted -> no cache_lookup span (observe, never pad)
    db.execute(_plans(db, ccfg, 2, seed=9), use_cache=False)
    nocache = rec.traces()[-2:]
    assert all("cache_lookup" not in [s.name for s in t.spans]
               for t in nocache)


def test_cache_hit_trace_short_circuits():
    db, ccfg = _db()
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    plans = _plans(db, ccfg, 2)
    db.execute(plans)                       # cold: full pipeline
    db.execute(plans)                       # warm: cache hits
    hits = [t for t in rec.traces()
            if any(s.name == "cache_lookup" and s.ann.get("outcome") == "hit"
                   for s in t.spans)]
    assert len(hits) == len(plans)
    for t in hits:
        _assert_well_formed(t)
        names = [s.name for s in t.spans]
        assert "launch" not in names        # hit never reaches the device
        assert t.root.ann["served"] == "cache"


def test_scheduler_trace_adds_queue_and_plan_spans():
    db, ccfg = _db()
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    clock = FakeClock()
    sched = Scheduler(db, SchedulerConfig(slo_ms=1e9, max_queue=16,
                                          max_batch=4, degrade_pressure=2.0,
                                          stale_pressure=2.0),
                      clock=clock, metrics=MetricsRegistry(),
                      sleep=clock.advance)
    for i, plan in enumerate(_plans(db, ccfg, 3)):
        assert sched.offer(ServeRequest(plan=plan, arrival_t=clock(),
                                        req_id=i, tenant=i % 3))
    results = sched.run_until_idle()
    assert len(results) == 3
    assert len(rec.traces()) == 3
    for t in rec.traces():
        _assert_well_formed(t)
        names = [s.name for s in t.spans]
        # the plans were compiled while tracing: each read's trace starts
        # at its compile, a closed span before the queue wait
        assert names[:3] == ["request", "plan_compile", "queue"]
        assert "degrade" in names and "launch" in names
        assert t.root.ann["deadline_met"] is True
        assert "e2e_ms" in t.root.ann and "req_id" in t.root.ann


# -- disabled path: bit-identity and true zero-cost ------------------------

def test_tracer_disabled_results_bit_identical():
    db, ccfg = _db()
    plans = _plans(db, ccfg, 4)
    assert not db.tracer.enabled            # off is the default
    off = db.execute(plans, use_cache=False)
    db.attach_tracer(Tracer(enabled=True, recorder=FlightRecorder()))
    on = db.execute(plans, use_cache=False)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)
    assert db.tracer.traces_started == len(plans)
    db.attach_tracer(Tracer(enabled=False))
    db.execute(plans, use_cache=False)
    assert db.tracer.traces_started == 0    # disabled path makes no traces


# -- spans on the profiler's clock -----------------------------------------

def _profiled(log_dir, fn):
    """Run ``fn`` under a profiler session writing into ``log_dir``; return
    its result and the ``rag.*`` host events as (name, stats) pairs."""
    jax.profiler.start_trace(str(log_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                     "*.xplane.pb"))
    events = [(ev.name, dict(ev.stats))
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("rag.")]
    return out, events


def _serve(db, plans, max_batch):
    clock = FakeClock()
    sched = Scheduler(db, SchedulerConfig(slo_ms=1e9, max_queue=64,
                                          max_batch=max_batch,
                                          degrade_pressure=2.0,
                                          stale_pressure=2.0,
                                          use_cache=False),
                      clock=clock, metrics=MetricsRegistry(),
                      sleep=clock.advance)
    for i, plan in enumerate(plans):
        assert sched.offer(ServeRequest(plan=plan, arrival_t=clock(),
                                        req_id=i))
    return sorted(sched.run_until_idle(), key=lambda r: r.request.req_id)


def test_enabled_tracer_writes_rag_events(tmp_path):
    db, ccfg = _db()
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))

    def run():
        return _serve(db, _plans(db, ccfg, 5), max_batch=2)

    results, events = _profiled(tmp_path, run)
    assert len(results) == 5
    by_name: dict = {}
    for name, stats in events:
        by_name.setdefault(name, []).append(stats)
    for stage in ("queue", "plan_compile", "launch", "pending",
                  "device_sync", "merge"):
        assert f"rag.{stage}" in by_name, (stage, sorted(by_name))
    # per-request spans carry the request; per-unit spans the unit
    assert sorted(st["req_id"] for st in by_name["rag.queue"]) == \
        list(range(5))
    launched = sorted(st["unit"] for st in by_name["rag.launch"])
    assert len(launched) == 3               # batches of 2, 2, 1: one each
    assert len(set(launched)) == len(launched)
    for stage in ("device_sync", "merge"):
        assert sorted(st["unit"] for st in by_name[f"rag.{stage}"]) == \
            launched
    assert sorted(st["unit"] for st in by_name["rag.pending"]) == launched
    assert len(by_name["rag.plan_compile"]) == 5
    assert len(by_name["rag.request"]) == 5
    # the member traces' spans carry the unit of the profiler events
    for t in rec.traces():
        _assert_well_formed(t)
        units = {s.ann["unit"] for s in t.spans
                 if s.name in ("launch", "pending", "device_sync", "merge")}
        assert len(units) == 1 and units <= set(launched)
        names = [s.name for s in t.spans]
        assert names.index("launch") < names.index("pending") \
            < names.index("device_sync")


def test_fan_span_writes_one_event(tmp_path):
    tracer = Tracer(enabled=True)

    def run():
        traces = [tracer.trace("request", req_id=i) for i in range(3)]
        FanSpan(traces, "launch", unit=7).end(rows=3)
        for t in traces:
            t.finish()
        return traces

    traces, events = _profiled(tmp_path, run)
    launches = [st for name, st in events if name == "rag.launch"]
    assert launches == [{"unit": 7, "rows": 3}]
    for t in traces:                        # ... and a span in each trace
        (span,) = [s for s in t.spans if s.name == "launch"]
        assert span.ann == {"unit": 7, "rows": 3}


def test_disabled_tracer_writes_no_events(tmp_path):
    db, ccfg = _db()
    assert not db.tracer.enabled
    results, events = _profiled(
        tmp_path, lambda: _serve(db, _plans(db, ccfg, 3), max_batch=2))
    assert len(results) == 3
    assert events == []


def test_launch_span_carries_launched_shape():
    db, ccfg = _db()
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    rng = np.random.default_rng(3)
    sess = db.admin_session()
    # a dense launch holds its whole bucket in one query-row block
    for n, bucket in ((9, 16), (5, 8)):
        plans = [sess.search(rng.standard_normal(ccfg.dim).astype(np.float32),
                             normalize=False).using("pallas").limit(6).plan()
                 for _ in range(n)]
        db.execute(plans, use_cache=False)
        spans = [s for t in rec.traces()[-n:] for s in t.spans
                 if s.name == "launch"]
        assert len(spans) == n
        assert {s.ann["unit"] for s in spans} == {spans[0].ann["unit"]}
        ann = spans[0].ann
        assert (ann["family"], ann["rows"], ann["bucket"], ann["block_rows"],
                ann["passes"]) == ("filtered", n, bucket, bucket, 1)
    # the ref engine scans without the kernel: no passes to count
    db.execute(_plans(db, ccfg, 3), use_cache=False)
    ann = next(s.ann for s in rec.traces()[-1].spans if s.name == "launch")
    assert ann["family"] == "ref" and "passes" not in ann


@pytest.mark.parametrize("order,merged", [("descending", 1),
                                           ("ascending", 4)])
def test_device_sync_span_carries_merge_tally(order, merged):
    """A kernel unit's `device_sync` span carries the merge gate's tally:
    ``tiles_merged`` of ``tiles`` (tiles x lists x query blocks). Over an
    arena whose scores fall row by row for every query row, only the first
    of its four 512-row tiles holds a row of the top 10; where they rise,
    every tile does."""
    from repro.core.store import DocBatch
    n, dim = 2048, 8
    score = np.arange(n, 0, -1) if order == "descending" else np.arange(1, n + 1)
    emb = np.zeros((n, dim), np.float32)
    emb[:, 0] = score
    db = RagDB(StoreConfig(capacity=n, dim=dim, metric="dot"), now_ts=100)
    db.ingest(DocBatch(emb=jnp.asarray(emb),
                       tenant=jnp.zeros(n, jnp.int32),
                       category=jnp.zeros(n, jnp.int32),
                       updated_at=jnp.full(n, 100, jnp.int32),
                       acl=jnp.full(n, ALL_BITS, jnp.uint32),
                       doc_id=jnp.arange(n, dtype=jnp.int32)))
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    sess = db.admin_session()
    q = np.eye(dim, dtype=np.float32)[0]
    plans = [sess.search(q * (i + 1), normalize=False).using("pallas")
             .limit(10).plan() for i in range(3)]
    db.execute(plans, use_cache=False)
    (ann,) = {tuple(sorted(s.ann.items())) for t in rec.traces()[-3:]
              for s in t.spans if s.name == "device_sync"}
    ann = dict(ann)
    assert (ann["tiles_merged"], ann["tiles"]) == (merged, 4)
    # the ref engine runs no kernel: no tally
    db.execute([sess.search(q, normalize=False).limit(10).plan()],
               use_cache=False)
    ann = next(s.ann for s in rec.traces()[-1].spans
               if s.name == "device_sync")
    assert "tiles" not in ann


def _lexical_db(n_docs=300, dim=16):
    ccfg = CorpusConfig(n_docs=n_docs, dim=dim, n_tenants=3, n_categories=4,
                        vocab_size=256, n_entity_terms=32)
    db = RagDB(StoreConfig(capacity=512, dim=dim), now_ts=ccfg.now_ts,
               lexical_cfg=LexicalConfig(vocab_size=256,
                                         doc_terms=ccfg.doc_terms))
    db.ingest(make_corpus(ccfg))
    return db, ccfg


@pytest.mark.parametrize("family,n,bucket,block_rows", [
    ("grouped", 9, 16, 16),        # a dense bucket of 16: one arena pass
    ("grouped", 200, 256, 128),    # past the MXU width: 128-row blocks
    ("hybrid", 9, 16, 8),          # the BM25 stage keeps 8-row blocks
    ("hybrid", 3, 4, 8),
])
def test_launch_passes_follow_block_rows(family, n, bucket, block_rows):
    """`rag.launch`'s ``passes`` counts the arena streams the launch made:
    ceil(bucket / block_rows), with the block the scan wrappers take
    (`default_blk_b`)."""
    db, ccfg = _lexical_db() if family == "hybrid" else _db()
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    rng = np.random.default_rng(5)
    sess = db.admin_session()
    plans = []
    for i in range(n):
        b = sess.search(rng.standard_normal(ccfg.dim).astype(np.float32),
                        normalize=False)
        if family == "hybrid":
            b = b.match([int(t) for t in rng.integers(0, 256, 2)])
        else:
            # four predicate groups, fused into one scan
            b = b.newer_than(i % 4).using("pallas")
        plans.append(b.limit(6).plan())
    db.execute(plans, use_cache=False)
    (ann,) = {tuple(sorted(s.ann.items())) for t in rec.traces()[-n:]
              for s in t.spans if s.name == "launch"}
    ann = dict(ann)
    assert (ann["family"], ann["rows"], ann["bucket"], ann["block_rows"]) \
        == (family, n, bucket, block_rows)
    assert ann["passes"] == -(-bucket // block_rows)


def test_launch_span_carries_lexical_loop():
    """A hybrid unit's `rag.launch` carries its fusion ``mode``, the
    query-term bucket ``qt`` it ran at, the buckets it joined
    (``qt_joined``), the real terms of its rows (``qterms``), the postings
    ``lanes``, and the scheduler ``batch`` it was launched in, which the
    units of one batch share; a dense launch carries its batch too. The
    wsum reads of buckets 1 and 4 join into one launch at qt 4."""
    db, ccfg = _lexical_db()
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    rng = np.random.default_rng(9)
    sess = db.admin_session()
    reads = [("wsum", [1]), ("wsum", [2]), ("wsum", [3, 4, 5]),
             ("rrf", [6, 7, 8, 9, 10]), ("rrf", [11, 12, 13, 14, 15])]
    plans = [sess.search(rng.standard_normal(ccfg.dim).astype(np.float32),
                         normalize=False).match(t).fuse(m).limit(6).plan()
             for m, t in reads]
    plans += [sess.search(rng.standard_normal(ccfg.dim).astype(np.float32),
                          normalize=False).limit(6).plan()]
    _serve(db, plans, max_batch=5)
    launches = {s.ann["unit"]: s.ann for t in rec.traces() for s in t.spans
                if s.name == "launch"}
    hybrid = sorted(((a["mode"], a["qt"], a["rows"], a["qterms"],
                      a["lanes"], a["batch"], a["qt_joined"])
                     for a in launches.values() if a["family"] == "hybrid"))
    assert hybrid == [("rrf", 8, 2, 10, ccfg.doc_terms, 1, 1),
                      ("wsum", 4, 3, 5, ccfg.doc_terms, 1, 2)]
    (dense,) = [a for a in launches.values() if a["family"] != "hybrid"]
    assert dense["batch"] == 2 and "qterms" not in dense


def test_results_bit_identical_under_profiler(tmp_path):
    db, ccfg = _db()
    plans = _plans(db, ccfg, 5)
    off = _serve(db, plans, max_batch=4)
    db.attach_tracer(Tracer(enabled=True, recorder=FlightRecorder()))
    on, events = _profiled(tmp_path, lambda: _serve(db, plans, max_batch=4))
    assert events                           # the tracer did write events
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a.scores, b.scores)
        np.testing.assert_array_equal(a.slots, b.slots)


# -- flight-recorder pinning rules -----------------------------------------

def test_recorder_ring_bounded_and_pins_survive():
    rec = FlightRecorder(cap=4, pin_cap=2)
    tr = Tracer(enabled=True, recorder=rec)
    for i in range(20):
        t = tr.trace("request", req_id=i)
        if i in (1, 5, 9):                  # 3 pinned > pin_cap=2
            t.pin("failed")
        t.finish()
    assert rec.recorded == 20
    assert len(rec.ring) == 4               # ring bound holds
    assert [t.root.ann["req_id"] for t in rec.ring] == [16, 17, 18, 19]
    # first pin_cap pinned traces retained even after the ring rolled
    assert [t.root.ann["req_id"] for t in rec.pinned] == [1, 5]
    assert rec.pin_drops == 1               # the refused third pin counted
    # pinned-first, deduplicated view + root-annotation lookup
    assert [t.root.ann["req_id"] for t in rec.traces()][:2] == [1, 5]
    assert [t.root.ann["req_id"] for t in rec.find(req_id=5)] == [5]


def test_degraded_and_fault_pins_applied_on_serving_path():
    db, ccfg = _db(tiered=True)
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    db.attach_faults(FaultPlan(0, {"warm.error": FaultRule(rate=1.0)}))
    clock = FakeClock()
    sched = Scheduler(db, SchedulerConfig(slo_ms=1e9, max_queue=16,
                                          max_batch=4, degrade_pressure=2.0,
                                          stale_pressure=2.0, warm_retries=0),
                      clock=clock, metrics=MetricsRegistry(),
                      sleep=clock.advance)
    rng = np.random.default_rng(0)
    plan = db.admin_session().search(
        rng.standard_normal(ccfg.dim).astype(np.float32),
        normalize=False).limit(6).plan()
    assert plan.route == "hot+warm"
    sched.offer(ServeRequest(plan=plan, arrival_t=clock(), req_id=0))
    (res,) = sched.run_until_idle()
    assert res.degraded                     # warm tier failed over
    (t,) = rec.find(req_id=0)
    assert "degraded" in t.pins and "fault" in t.pins
    assert t.root.ann["degraded"]           # names the rung
    faults = [site for s in t.spans for site in s.ann.get("faults", ())]
    assert "warm.error" in faults           # the injected site, by name


def test_failed_request_trace_pins_failed_with_fault_annotation():
    db, ccfg = _db()
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    db.attach_faults(FaultPlan(0, {"hot.launch": FaultRule(rate=1.0)}))
    clock = FakeClock()
    sched = Scheduler(db, SchedulerConfig(slo_ms=1e9, max_queue=16,
                                          max_batch=4, degrade_pressure=2.0,
                                          stale_pressure=2.0,
                                          launch_retries=0, requeue_limit=0),
                      clock=clock, metrics=MetricsRegistry(),
                      sleep=clock.advance)
    (plan,) = _plans(db, ccfg, 1)
    sched.offer(ServeRequest(plan=plan, arrival_t=clock(), req_id=7))
    (res,) = sched.run_until_idle()
    assert res.served == "failed"
    (t,) = rec.find(req_id=7)
    assert "failed" in t.pins and "fault" in t.pins
    assert t.root.ann["served"] == "failed"
    faults = [site for s in t.spans for site in s.ann.get("faults", ())]
    assert "hot.launch" in faults


# -- Perfetto export round-trip --------------------------------------------

def test_perfetto_export_round_trips_and_matches_offline_tool():
    db, ccfg = _db()
    rec = FlightRecorder()
    db.attach_tracer(Tracer(enabled=True, recorder=rec))
    db.execute(_plans(db, ccfg, 3), use_cache=False)

    d = json.loads(json.dumps(rec.to_perfetto()))   # JSON round-trip
    events = d["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(metas) == len(rec.traces())
    n_closed = sum(1 for t in rec.traces() for s in t.spans
                   if s.t1 is not None)
    assert len(xs) == n_closed
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0       # normalized to t_base
        assert {"span_id", "parent_id"} <= set(e["args"])
        assert e["cat"] == "serve"
    # every X event's tid maps to a declared pseudo-thread
    assert {e["tid"] for e in xs} <= {e["tid"] for e in metas}

    # the offline tool rebuilds the identical event list from a dump
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(__file__), "..",
                                     "tools", "trace_report.py"))
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)
    dump = json.loads(json.dumps(rec.to_dict()))
    assert dump["schema"] == "repro.obs.flight_recorder/v1"
    assert trace_report.to_perfetto(dump) == d


# -- calibration audit -----------------------------------------------------

def test_calibration_always_on_and_keyed_by_shape():
    db, ccfg = _db(measured=True)
    assert not db.tracer.enabled
    k = 6
    plans = _plans(db, ccfg, 4, k=k)
    db.execute(plans, use_cache=False)
    cal = db.calibration
    assert cal.recorded > 0                 # tracer off, audit still on
    (key,) = cal.units
    engine, nb, groups, kk = key
    assert engine == plans[0].engine
    assert nb == pow2_bucket(plans[0].n_rows) and kk == k
    u = cal.units[key]
    assert u["rows"] == len(plans)
    assert u["priced"] == u["count"] and u["predicted_ms"] > 0
    assert u["device_ms"] >= u["launch_ms"] > 0
    snap = cal.snapshot()
    assert snap["engines"][engine]["ratio"] is not None
    assert "calibration:" in db.explain()


def test_cost_model_calibrated_rescales_by_drift():
    cm = CostModel(curves=(("ref", ((1000, 1.0), (4000, 4.0))),
                           ("ivf", ((1000, 0.5), (4000, 2.0)))))
    base = cm.estimate_ms("ref", 1000)
    t = CalibrationTable()
    t.record_unit(engine="ref", n_rows=1000, groups=8, k=8, rows=8,
                  predicted_ms=2.0, launch_ms=1.0, sync_ms=3.0,
                  rows_scanned=1000)        # measured 2x the prediction
    cal = cm.calibrated(t)
    assert cal.estimate_ms("ref", 1000) == pytest.approx(2 * base)
    # identity cases: no table, empty table, engine without drift data
    assert cm.calibrated(None) is cm
    assert cm.calibrated(CalibrationTable()) is cm
    assert cm.calibrated(t).estimate_ms("ivf", 1000) == \
        pytest.approx(cm.estimate_ms("ivf", 1000))
