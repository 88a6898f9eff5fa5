"""One run of one benchmark cell.

    set-up   make the corpus on the device from the seed and ingest it
             through `RagDB.ingest`; make the seeded schedule; warm every
             program shape the cell's traffic can reach;
    window   offer each read at its due time (`ServeRequest.arrival_t`) to
             `serving.Scheduler` and apply each write through
             `RagDB.update` in due order, for ``--seconds``; then wait for
             every read to finish (at most `DRAIN_S` past the close);
    check    read the peak device memory and the written rows back, free
             the system's state, hold every row of every finished read to
             its isolation predicates, and compare a seeded sample of the
             served answers with the plain reference (`bench.reference`).

Every latency runs from the operation's due time to the moment its result
is available, so a stall shows in every request it delays.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from bench import trace as tracing
from bench.corpus import Corpus, CorpusShape
from bench.metrics import reader
from bench.reference import Lexical, Read, Reference
from bench.traffic import ALL_BITS, make_schedule

from repro import runtime
from repro.api import RagDB
from repro.api.plan import bucket_rows
from repro.core.store import DocBatch, StoreConfig
from repro.core.tenancy import Principal
from repro.index.lexical import LexicalConfig
from repro.obs import Tracer
from repro.serving.scheduler import Scheduler, SchedulerConfig, ServeRequest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
DRAIN_S = 60.0
WRITE = 1                          # `Schedule.kind` of a write


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def configure_cache() -> str:
    """The program's persistent compilation cache (`JAX_COMPILATION_CACHE_DIR`
    where set, else ``<checkout>/.jax_cache``), keeping every program
    however fast it compiled, so that only a cell's first run in a
    checkout compiles. Call before the first compile."""
    path = runtime.configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its configuration
    file and its traffic mix (``bench/traffic/<traffic>.json``)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    work = {w["name"]: w for w in bm["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", f"{w['traffic']}.json")) as f:
        mix = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bm["per_layer"] if _applies(m, name)])


def corpus_shape(config: dict) -> CorpusShape:
    c = dict(config["corpus"])
    lex = config.get("lexical")
    kw = {}
    if lex is not None:
        kw = dict(lexical=True, vocab_size=lex["vocab_size"],
                  doc_terms=lex["doc_terms"])
    return CorpusShape(dim=int(config["store"]["dim"]), **c, **kw)


def peaks_of(kind: str) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class System:
    """The system under test and everything the window drives it with."""
    cell: Cell
    seed: int
    corpus: Corpus
    db: RagDB
    meta: dict
    schedule: object
    writes_done: int = 0


def build(cell: Cell, seed: int, seconds: float, *, rate=None,
          say=print) -> System:
    config = cell.config
    shape = corpus_shape(config)
    corpus = Corpus(shape, seed)
    st = config["store"]
    lex = config.get("lexical")
    lex_cfg = None if lex is None else LexicalConfig(
        vocab_size=lex["vocab_size"], doc_terms=lex["doc_terms"],
        max_query_terms=lex["max_query_terms"], k1=lex["k1"], b=lex["b"],
        rrf_c=lex["rrf_c"])
    db = RagDB(StoreConfig(capacity=int(st["capacity"]), dim=int(st["dim"]),
                           metric=st["metric"], dtype=st["dtype"]),
               lexical_cfg=lex_cfg,
               result_cache_size=int(config["serving"]["result_cache"]))
    meta = {"tenant": np.empty(shape.n_docs, np.int32),
            "topic": np.empty(shape.n_docs, np.int32),
            "last_term": np.full(shape.n_docs, -1, np.int32)}
    t0 = time.perf_counter()
    for c in range(shape.n_chunks):
        lo, hi = shape.chunk_span(c)
        with jax.profiler.TraceAnnotation("bench.ingest"):
            cols = corpus.chunk(c)
            meta["tenant"][lo:hi] = np.asarray(cols["tenant"])
            meta["topic"][lo:hi] = np.asarray(cols["topic"])
            if lex is not None:
                meta["last_term"][lo:hi] = np.asarray(cols["terms"][:, -1])
            db.ingest(DocBatch(emb=cols["emb"], tenant=cols["tenant"],
                               category=cols["category"],
                               updated_at=cols["updated_at"], acl=cols["acl"],
                               doc_id=cols["doc_id"], terms=cols.get("terms"),
                               tfs=cols.get("tfs")))
            del cols
    jax.block_until_ready(db.log.snapshot()["emb"])
    say(f"ingest: {shape.n_docs} docs in {shape.n_chunks} chunks, "
        f"{time.perf_counter() - t0:.3f}s")
    schedule = make_schedule(cell.mix, corpus, seed, seconds, meta=meta,
                             rate=rate)
    return System(cell=cell, seed=seed, corpus=corpus, db=db, meta=meta,
                  schedule=schedule)


def shape_signatures(max_batch: int, fuse_min_groups: int) -> dict:
    """Every launch shape a unit of n rows in g predicate groups can take
    (the executor's bucketing: rows to a power of two, groups to a power
    of two with one blocker lane opened when padding rows need one), with
    one (n, g) that reaches it."""
    out: dict = {}
    for n in range(1, max_batch + 1):
        for g in range(1, n + 1):
            b = bucket_rows(n)
            if g < fuse_min_groups:
                sig = ("solo", b)
            else:
                gb = bucket_rows(g)
                if b > n and gb == g:
                    gb = bucket_rows(g + 1)
                sig = ("fused", b, gb)
            out.setdefault(sig, (n, g))
    return out


def _lower(db: RagDB, sched, r: int, now_ts: int):
    """Read ``r`` of the schedule through the front door: its session,
    clauses and LIMIT, compiled by the planner."""
    lv = sched.levels[sched.level[r]]
    if lv.scope == "admin":
        sess = db.admin_session()
    else:
        sess = db.session(Principal(tenant_id=int(sched.tenant[r]),
                                    group_bits=lv.acl_bits))
    b = sess.search(sched.q[r])
    if lv.newer_than_days is not None:
        b = b.newer_than(lv.min_ts(now_ts))
    if lv.categories is not None:
        b = b.in_categories(lv.categories)
    if sched.terms is not None:
        b = b.match(list(sched.terms[r])).fuse(str(sched.mode[r]))
    return b.limit(sched.k).plan()


def warm(system: System, say=print) -> int:
    """Run one batch of every launch shape the cell's traffic can reach:
    each fuse key (score mix and query-term bucket) the schedule holds,
    times every (rows, groups) bucket up to the scheduler's batch. Then,
    where the mix writes, the set-up write (row 0 of the write log); last,
    the objects set-up made are moved out of the collector's reach.
    Returns the number of warm batches."""
    db, sched = system.db, system.schedule
    max_batch = int(system.cell.config["serving"]["max_batch"])
    # hybrid units always launch grouped (a single group is G = 1)
    sigs = shape_signatures(max_batch, 1 if sched.terms is not None
                            else db.planner_cfg.fuse_min_groups)
    keys = [None]
    if sched.terms is not None:
        qts = sorted({bucket_rows(len(t)) for t in sched.terms})
        keys = [(m, qt) for m in sorted(set(sched.mode)) for qt in qts]
    q = system.corpus.points(5, max_batch)
    n_tenants = system.corpus.shape.n_tenants
    count = 0
    for key in keys:
        for n, g in sigs.values():
            if g > n_tenants:
                continue
            plans = []
            for r in range(n):
                b = db.session(Principal(tenant_id=r % g,
                                         group_bits=ALL_BITS)).search(q[r])
                if key is not None:
                    b = b.match(list(range(key[1]))).fuse(key[0])
                plans.append(b.limit(sched.k).plan())
            with jax.profiler.TraceAnnotation("bench.warm"):
                db.execute(plans, use_cache=False)
            count += 1
    if sched.n_writes:
        _write(system, 0)
    # what set-up built lives as long as the process: keep the collector
    # from walking it in every full collection inside the window
    gc.collect()
    gc.freeze()
    return count


def _write(system: System, w: int) -> None:
    s = system.schedule
    system.db.update([int(s.w_doc[w])], s.w_emb[w][None, :],
                     [int(s.w_ts[w])])
    system.writes_done = w + 1


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

class SpanCollector:
    """Keeps every finished request trace of the traced run."""

    def __init__(self):
        self.traces: list = []

    def record(self, trace) -> None:
        self.traces.append(trace)


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t_end: float = 0.0             # last completion (or the drain limit)
    served: dict = dataclasses.field(default_factory=dict)
    # read index -> ServedResult
    writes_before: dict = dataclasses.field(default_factory=dict)
    # read index -> writes committed when its batch launched
    write_ms: list = dataclasses.field(default_factory=list)
    plan_ms: list = dataclasses.field(default_factory=list)
    late_ms: list = dataclasses.field(default_factory=list)
    # offer time - due time, every op
    compiles: int = 0
    spans: list = dataclasses.field(default_factory=list)


def _admit(system: System, sched: Scheduler, win: Window, i: int) -> int:
    """Offer every read and apply every write due by now, in due order;
    returns the index of the next op."""
    sch = system.schedule
    now_ts = system.corpus.shape.now_ts
    clock = sched.clock
    while i < len(sch.t) and win.t0 + sch.t[i] <= clock():
        due = win.t0 + sch.t[i]
        j = int(sch.index[i])
        win.late_ms.append((clock() - due) * 1e3)
        if sch.kind[i] == WRITE:
            with jax.profiler.TraceAnnotation("bench.write"):
                _write(system, j)
            win.write_ms.append((clock() - due) * 1e3)
        else:
            with jax.profiler.TraceAnnotation("bench.plan"):
                p0 = time.perf_counter()
                plan = _lower(system.db, sch, j, now_ts)
                win.plan_ms.append((time.perf_counter() - p0) * 1e3)
                sched.offer(ServeRequest(plan=plan, arrival_t=due, req_id=j,
                                         tenant=int(sch.tenant[j])))
        i += 1
    return i


def _step(system: System, sched: Scheduler, win: Window,
          max_batch: int) -> None:
    """One scheduler round. The round launches the queue's head (FIFO, one
    k), so the reads it takes see the writes committed so far."""
    launching = [r.req_id for r, _ in zip(sched.queue, range(max_batch))]
    depth = len(sched.queue)
    with jax.profiler.TraceAnnotation("bench.step"):
        out = sched.step()
    for rid in launching[:depth - len(sched.queue)]:
        win.writes_before[rid] = system.writes_done
    for res in out:
        win.served[res.request.req_id] = res


def drive(system: System, seconds: float, *, max_batch: int,
          traced: bool = False) -> Window:
    """Offer the schedule open-loop: each op at its due time, whatever the
    system's state; then finish what is in flight."""
    db, sch = system.db, system.schedule
    collector = None
    if traced:
        collector = SpanCollector()
        db.attach_tracer(Tracer(enabled=True, recorder=collector))
    sched = Scheduler(db, SchedulerConfig(
        admission=False, max_batch=max_batch, use_cache=True,
        stale_within_s=None))
    clock = sched.clock
    win = Window()

    def on_compile(name, secs, **_):
        if name.endswith("backend_compile_duration"):
            win.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            win.t0 = clock()
            deadline = win.t0 + seconds + DRAIN_S
            i = 0
            while clock() <= deadline:
                i = _admit(system, sched, win, i)
                if sched.busy:
                    _step(system, sched, win, max_batch)
                elif i < len(sch.t):
                    with jax.profiler.TraceAnnotation("bench.idle"):
                        time.sleep(max(0.0, win.t0 + sch.t[i] - clock()))
                else:
                    break
            win.t_end = clock()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    if traced:
        db.attach_tracer(Tracer(enabled=False))
        win.spans = collector.traces
    return win


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def sample_reads(system: System, win: Window, n: int) -> list:
    """A seeded sample of the finished reads, as `Read`s."""
    sch = system.schedule
    now_ts = system.corpus.shape.now_ts
    done = sorted(r for r, res in win.served.items() if res.served != "failed")
    rng = np.random.default_rng([system.seed, 0x5A3B1E])
    pick = sorted(rng.choice(done, size=min(n, len(done)), replace=False))
    out = []
    for r in pick:
        res = win.served[r]
        spec = sch.read_spec(r, now_ts)
        out.append(Read(q=sch.q[r], writes_before=win.writes_before[r],
                        slots=np.asarray(res.slots[0], np.int64),
                        scores=np.asarray(res.scores[0], np.float64),
                        **spec))
    return out


def finished_rows(system: System, win: Window) -> tuple:
    """Every finished read's predicate row [tenant, min_ts, cat_mask,
    acl_bits], served doc ids and writes committed before its launch."""
    sch = system.schedule
    now_ts = system.corpus.shape.now_ts
    done = sorted(r for r, res in win.served.items() if res.served != "failed")
    preds = np.zeros((len(done), 4), np.int64)
    slots = np.full((len(done), sch.k), -1, np.int64)
    for i, r in enumerate(done):
        spec = sch.read_spec(r, now_ts)
        preds[i] = (spec["tenant"], spec["min_ts"], spec["cat_mask"],
                    spec["acl_bits"])
        got = np.asarray(win.served[r].slots[0], np.int64)
        slots[i, :len(got)] = got
    wb = np.asarray([win.writes_before[r] for r in done], np.int64)
    return preds, slots, wb


def make_reference(system: System) -> Reference:
    lex = system.cell.config.get("lexical")
    lexical = None if lex is None else Lexical(
        k1=lex["k1"], b=lex["b"], rrf_c=lex["rrf_c"],
        max_query_terms=lex["max_query_terms"])
    s = system.schedule
    n = system.writes_done
    return Reference(system.corpus, s.k, lexical,
                     (s.w_doc[:n], s.w_emb[:n], s.w_ts[:n]))


def readback(system: System) -> tuple:
    """The written docs' rows as the arena holds them (slot = doc id: the
    arena fills free slots in doc id order and the cells delete nothing)."""
    s = system.schedule
    ids = np.unique(s.w_doc[:system.writes_done])
    snap = system.db.log.snapshot()
    dev = jnp.asarray(ids, jnp.int32)
    return (np.asarray(snap["emb"][dev]), np.asarray(snap["updated_at"][dev]))


def compare(ref: Reference, reads: list, served: tuple, limits: dict):
    """Numbers compared with their limits: (checks, detail)."""
    t0 = time.perf_counter()
    lists = ref.scan(reads)
    t1 = time.perf_counter()
    j = ref.judge(reads, lists, served, tol=limits["score_gap"])
    j["scan_s"], j["judge_s"] = t1 - t0, time.perf_counter() - t1
    checks = {"score_gap": (j["score_gap"], limits["score_gap"])}
    if ref.lex is not None:
        checks["rrf_mismatch"] = (j["rrf_mismatch"], 0)
    checks["ref_undecided"] = (j["ref_undecided"], 0)
    return checks, j


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader reads (see `bench.metrics`)."""
    config: dict
    peaks: dict
    max_batch: int
    plan_ms: list
    queue_wait_ms: list
    spans: list
    profile: object
    engines: Counter
    writes_in_window: int


def pct(values, p) -> float:
    """Exact percentile over every sample (linear interpolation between
    order statistics, as `serving.metrics.Histogram` computes it)."""
    return float(np.percentile(np.asarray(values, np.float64), p))


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_proc0: float, say=print, peaks: dict | None = None) -> dict:
    """One run; returns the result line's object. ``peaks`` defaults to the
    device's row of bench/peaks.json."""
    dev = jax.devices()[0]
    config = cell.config
    max_batch = int(config["serving"]["max_batch"])
    system = build(cell, seed, seconds, say=say)
    n_warm = warm(system, say=say)
    say(f"warm-up: {n_warm} batches")
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracing.start(TRACE_DIR)
    setup_s = time.perf_counter() - t_proc0
    win = drive(system, seconds, max_batch=max_batch, traced=trace)
    profile = None
    if trace:
        jax.profiler.stop_trace()
        profile = tracing.load(TRACE_DIR)
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    sch = system.schedule
    n_reads, n_writes = sch.n_reads, sch.n_writes
    done = [r for r in win.served.values() if r.served != "failed"]
    lat = [r.e2e_ms for r in done]
    late = np.asarray(win.late_ms)
    say(f"window: {n_reads} reads and {n_writes} writes due in {seconds}s; "
        f"{len(done)} reads finished, last at +{win.t_end - win.t0:.3f}s; "
        f"generator late p50 {np.median(late):.3f} ms, p99 "
        f"{np.percentile(late, 99):.3f} ms, max {late.max():.3f} ms; "
        f"{win.compiles} compiles inside the window; "
        f"{sum(1 for r in done if r.served == 'cache')} result-cache hits")
    metrics = {}
    values = {"p50_ms": pct(lat, 50) if lat else None,
              "p95_ms": pct(lat, 95) if lat else None,
              "qps": len(done) / max(win.t_end - win.t0, 1e-9),
              "write_p95_ms": (pct(win.write_ms, 95) if win.write_ms
                               else None),
              "setup_s": setup_s}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    engines = Counter(r.request.plan.engine for r in done)
    if not trace:
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": units[m["name"]]}
    else:
        rec = RunRecord(config=config,
                        peaks=peaks or peaks_of(dev.device_kind),
                        max_batch=max_batch, plan_ms=win.plan_ms,
                        queue_wait_ms=[r.queue_wait_ms for r in done],
                        spans=win.spans, profile=profile, engines=engines,
                        writes_in_window=len(win.write_ms))
        for m in cell.per_layer:
            v = reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        device["busy_s"] = profile.busy_s()
        device["window_s"] = profile.window_s()
        ops = sorted(profile.op_seconds().items(), key=lambda x: -x[1])
        gaps = sorted(profile.idle_gaps().items(), key=lambda x: -x[1])
        breakdown = {"device_ops": [[n, s] for n, s in ops[:10]],
                     "idle_gaps": [[n, s] for n, s in gaps[:10]]}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # -- the check: after the window, with the system's state freed ------
    limits = config["check"]["limits"]
    checks = {"unanswered": (n_reads - len(done), 0)}
    if n_writes:
        emb, ts = readback(system)
        ref_w = make_reference(system)
        checks["readback_gap"] = (float(ref_w.readback_gap(emb, ts)),
                                  limits["readback_gap"])
    reads = sample_reads(system, win, int(config["check"]["sample_reads"]))
    every = finished_rows(system, win)
    system.db = None
    del win, done
    gc.unfreeze()
    gc.collect()
    ref = make_reference(system)
    served = (np.stack([r.slots for r in reads]),
              np.stack([r.scores for r in reads]))
    t_ref = time.perf_counter()
    checks["isolation_violations"] = (ref.isolation(*every), 0)
    t_iso = time.perf_counter() - t_ref
    cmp, detail = compare(ref, reads, served, limits)
    checks.update(cmp)
    say(f"reference: isolation of {every[1].size} rows of "
        f"{len(every[1])} finished reads in {t_iso:.3f}s; "
        f"{detail['rows']} sampled reads checked in "
        f"{time.perf_counter() - t_ref:.3f}s (scan {detail['scan_s']:.3f}s, "
        f"judge {detail['judge_s']:.3f}s); {detail['rrf_tie_excused']} "
        f"rrf lists excused for a reference near-tie")
    correct = all(v <= lim for v, lim in checks.values())
    line = {"correct": bool(correct), "attempted": n_reads + n_writes,
            "failed": checks["unanswered"][0],
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    checks = {k: (float(v) if isinstance(v, float) else int(v), lim)
              for k, (v, lim) in checks.items()}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    return line
