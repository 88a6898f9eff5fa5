"""Table 1 — query latency: 4 complexity levels x Stack A/B, p50/p95/p99.

Reproduces the paper's crossover finding: equal latency on pure similarity,
split-system overhead growing with constraint count (round trips + app-side
merge + retry-on-underfill), unified latency flat or falling with selectivity.

Stack B goes through the front door (RagDB session -> builder -> planner ->
grouped executor), so the numbers include the full API path, and each query
type's compiled plan is recorded via explain().

A second section measures predicate-group batching: a B-request batch with G
unique predicate groups served as G stacked device calls (the RAGEngine.serve
fast path) versus the old per-request loop of B calls.

Two adaptive-serving sections (PR 2) close the loop:
  * `cost_model` — per-engine latency curves measured at several arena sizes,
    saved in the exact shape `repro.api.planner.CostModel.from_bench` loads,
    so the next serving process routes on THESE measurements instead of the
    static row thresholds;
  * `adaptive_serving` — the B=32/G=4 serve fast path through `db.execute`
    (bucketed + grouped, cache bypassed vs cache hit), plus a cold
    varying-batch-size stream showing bucketed batching amortizing program
    compilation (exact shapes recompile per distinct size; buckets don't).

The `ivf` section (PR 3) measures the sub-linear route: p50 vs nprobe at
several corpus sizes with recall@10 against the exact scan, the planner's
engine choice for an unconstrained group at each size, and the candidate-row
fraction from explain(). Its default-nprobe curve joins the `cost_model`
engines, so the planner prices the pruned scan from measurements too.

The `group_sweep` section (PR 4) measures grouped-scan fusion: a B=64 batch
with G distinct predicate groups, per-group loop (G arena streams) vs ONE
fused grouped_topk scan, at G in {1, 2, 4, 8, 16} on the 50k-doc arena —
with `rows_scanned` recorded both ways, so the G*N -> N claim is auditable
by count. `tools/check_bench_regression.py` gates CI on the G=8 point.
Run with ``--gsweep-only --out PATH`` for a fresh comparison file.

The `hybrid` section (PR 5) measures the lexical workload: fused one-pass
dense+BM25 (`kernels.hybrid_score`) vs the split two-scan+host-merge
baseline (`index.lexical.twoscan`) at N in {5k, 20k, 50k} — an "open" row
(no predicate, generous pushdown baseline: isolates the pure fusion win)
and a "composed" row (tenant+recency predicate, faithful Stack-A baseline
with app-layer post-filter and the over-fetch retry ladder: the paper's
crossover, reproduced for lexical+vector fusion) — plus keyword-anchored
recall@10 hybrid vs dense-only through the full session path, and the
planner's own engine choice for a match() query. The open fused curve
joins the `cost_model` engines. `tools/check_bench_regression.py
--hybrid-only` gates CI on the composed 50k point and the recall ordering.
Run with ``--hybrid-only --out PATH`` for a fresh comparison file.

The `paged_scan` section (PR 7) measures the paged arena-scan regime: the
same fused grouped scan with the arena streamed in page_rows-sized tiles
(double-buffered DMA in the Pallas kernel; page-sized jnp scan tiles on
CPU) vs VMEM-resident tiling, asserted bit-identical before timing.
`tools/check_bench_regression.py --paged-only` gates paged p50 within 15%
of resident at the 50k point. Run with ``--paged-only --out PATH`` for a
fresh comparison file.

The `sharded` section (PR 9) measures the shard-mapped arena scan: p50 at
N in {250k, 1M} x S in {1, 2, 4, 8} shards, the collective wire payload
read from the compiled HLO (the O(S*B*k) bound — three gathered (B, k)
k-lists, constant in corpus size), merge bit-identity against the
single-device lexicographic oracle, and the per-shard rows_scanned audit.
Multi-device CPU requires --xla_force_host_platform_device_count BEFORE
jax initializes, so the measurements run in ONE subprocess (this module
re-invoked with --sharded-worker) and return as JSON; the corpus streams
in via `data.corpus.stream_corpus`, so host memory stays O(chunk) at the
million-row point. The S=8 curve joins the `cost_model` engines.
`tools/check_bench_regression.py --sharded-only` gates every cell's
invariants plus the S=8 p50 (machine-normalized by the S=1 baseline).
Run with ``--sharded-only --out PATH`` for a fresh comparison file.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (PAPER, QUERY_TYPES, SESSION_QUERIES,
                               build_ragdb, build_stacks, percentiles,
                               save_result, timeit)
from repro.api import RagDB
from repro.api.executor import (CompiledShapes, ExecStats, run_grouped,
                                run_grouped_fused)
from repro.core import Predicate, Principal, StoreConfig, unified_query
from repro.core.ivf import ivf_query
from repro.core.query import stack_predicates
from repro.data.corpus import (DAY_S, CorpusConfig, make_corpus,
                               make_keyword_queries, make_queries)
from repro.index.lexical import LexicalConfig
from repro.index.lexical.twoscan import two_scan_hybrid
from repro.kernels.hybrid_score.ops import hybrid_score


def run(iters: int = 200, engine: str = "ref", n_docs: int = 50_000) -> dict:
    ccfg = CorpusConfig(n_docs=n_docs)
    _, split, corpus, (ccfg, scfg) = build_stacks(ccfg, with_unified=False)
    # result cache off: the paper table compares ENGINE latency against the
    # split stack; cached serving is measured in run_adaptive_serving below
    db, _, _ = build_ragdb(ccfg, corpus=corpus, result_cache_size=0)
    queries = make_queries(ccfg, 8, batch=1)
    k = 5

    table: dict[str, dict] = {}
    for qt, make_builder in SESSION_QUERIES.items():
        pred = QUERY_TYPES[qt](ccfg)
        sess_k = lambda q: (make_builder(db, ccfg, np.asarray(q)[0])
                            .limit(k).using(engine))
        plan_text = sess_k(queries[0]).explain()

        qi = [0]

        def q_unified():
            q = queries[qi[0] % len(queries)]
            sess_k(q).run()
            qi[0] += 1

        def q_split():
            q = queries[qi[0] % len(queries)]
            split.query(q, pred, k)
            qi[0] += 1

        b = percentiles(timeit(q_unified, iters=iters))
        a = percentiles(timeit(q_split, iters=iters))
        table[qt] = {"stack_a": a, "stack_b": b,
                     "speedup_p50": a["p50"] / max(b["p50"], 1e-9),
                     "plan": plan_text,
                     "paper": PAPER["latency_ms"][qt]}
        print(f"{qt:18s}  A p50={a['p50']:7.2f}ms  B p50={b['p50']:7.2f}ms  "
              f"(paper: A {PAPER['latency_ms'][qt]['A_p50']} / "
              f"B {PAPER['latency_ms'][qt]['B_p50']})")

    out = {"table": table, "iters": iters, "n_docs": ccfg.n_docs, "dim": ccfg.dim,
           "engine": engine,
           "split_round_trips": split.stats.round_trips,
           "split_retries": split.stats.retries,
           "batched_vs_looped": run_batched_vs_looped(
               db, ccfg, iters=max(iters // 4, 20), engine=engine, k=k),
           "cost_model": run_engine_curves(
               ccfg, iters=max(iters // 4, 20), k=k,
               warm_probe_ms=table["pure_similarity"]["stack_a"]["p50"]),
           "adaptive_serving": run_adaptive_serving(
               iters=max(iters // 4, 20), engine=engine, k=k)}
    out["ivf"] = run_ivf_curves(iters=max(iters // 4, 20))
    # the pruned scan joins the measured cost model: the next process's
    # planner prices ivf-vs-ref from these curves
    out["cost_model"]["engines"]["ivf"] = out["ivf"]["cost_curve"]
    out["group_sweep"] = run_group_sweep(iters=max(iters // 4, 20),
                                         engine=engine, db=db, ccfg=ccfg)
    out["paged_scan"] = run_paged_section(iters=max(iters // 4, 20),
                                          engine=engine, db=db, ccfg=ccfg)
    out["hybrid"] = run_hybrid_section(iters=max(iters // 4, 20))
    # the fused hybrid scan joins the measured cost model: the planner
    # prices (and explain() annotates) match() plans from these curves
    out["cost_model"]["engines"]["hybrid"] = out["hybrid"]["cost_curve"]
    out["sharded"] = run_sharded_section(iters=max(iters // 20, 5))
    # the shard-mapped scan joins the measured cost model at S=8: a
    # mesh-built RagDB prices 'sharded' from these curves
    out["cost_model"]["engines"]["sharded"] = out["sharded"]["cost_curve"]
    save_result("bench_latency", out)
    return out


def run_hybrid_section(*, iters: int, k: int = 10, batch: int = 8,
                       sizes=(5_000, 20_000, 50_000),
                       n_recall: int = 24) -> dict:
    """The lexical workload, measured: fused one-pass dense+BM25 vs the
    split two-scan+host-merge baseline, per corpus size.

    Two rows per size mirror the paper's Table-1 crossover:
      * "open"     — no predicate; the baseline gets GENEROUS pushdown
                     sidecars, so the gap is pure fusion overhead
                     (2 scans + 2 rescore gathers + host merge vs 1 pass);
      * "composed" — tenant+recency predicate; the baseline runs the
                     faithful split pipeline (unfiltered sidecars,
                     app-layer post-filter, over-fetch retry ladder) — the
                     regime the hybrid engine exists for. The 50k row is
                     the PR's acceptance bar (fused >= 1.5x) and the point
                     `check_bench_regression.py --hybrid-only` gates.

    Keyword-anchored recall@10 (hybrid vs dense-only, full session path)
    and the planner's engine choice for a match() query are recorded per
    size; the open fused curve is saved in `CostModel.from_bench` shape."""
    out = {"k": k, "batch": batch, "n_recall": n_recall, "sizes": {},
           "cost_curve": []}
    for n_docs in sizes:
        ccfg = CorpusConfig(n_docs=n_docs)
        db, corpus, (ccfg, scfg) = build_ragdb(
            ccfg, result_cache_size=0,
            lexical_cfg=LexicalConfig(vocab_size=ccfg.vocab_size,
                                      doc_terms=ccfg.doc_terms))
        arena = scfg.capacity
        q, qterms_list, relevant = make_keyword_queries(
            ccfg, corpus, max(batch, n_recall), seed=9)
        Q = q[:batch]
        QT = np.asarray([[t[0]] for t in qterms_list[:batch]], np.int32)
        snap = db.log.snapshot()
        lex = db.lex.snapshot()
        gids = np.zeros(batch, np.int32)
        composed = Predicate(tenant=3, min_ts=ccfg.now_ts - 120 * DAY_S)
        row = {"arena_rows": arena, "n_docs": n_docs}
        for label, pred, pushdown in (("open", Predicate(), True),
                                      ("composed", composed, False)):
            preds = stack_predicates([pred])

            def fused():
                s, _ = hybrid_score(
                    Q, snap["emb"], snap["tenant"], snap["updated_at"],
                    snap["category"], snap["acl"], lex["terms"],
                    lex["lexnorm"], lex["idf"], gids, preds, QT, k)
                jax.block_until_ready(s)

            def twoscan():
                two_scan_hybrid(snap, lex, Q, QT, pred, k,
                                pushdown=pushdown)

            t_f = percentiles(timeit(fused, iters=iters))
            t_t = percentiles(timeit(twoscan, iters=iters))
            row[label] = {
                "fused_ms": t_f, "twoscan_ms": t_t,
                "baseline": "pushdown sidecars" if pushdown
                            else "post-filter + retry ladder",
                "speedup_p50": t_t["p50"] / max(t_f["p50"], 1e-9)}
            print(f"hybrid: N={n_docs:6d} {label:9s} "
                  f"fused p50={t_f['p50']:7.2f}ms  "
                  f"two-scan p50={t_t['p50']:7.2f}ms  "
                  f"{row[label]['speedup_p50']:4.2f}x")
        # recall@10, full session path: dense-only vs hybrid on the
        # keyword-anchored grid (the workload's reason to exist)
        doc_ids = np.asarray(snap["doc_id"])
        admin = db.admin_session()

        def recall(match: bool) -> float:
            total = 0.0
            for i in range(n_recall):
                b = admin.search(q[i])
                if match:
                    b = b.match(qterms_list[i])
                res = b.limit(10).run()
                got = {int(doc_ids[s]) for s in res.slots[0] if s >= 0}
                rel = set(relevant[i].tolist())
                total += len(got & rel) / min(10, len(rel))
            return total / n_recall

        row["recall_at_10"] = {"dense": recall(False),
                               "hybrid": recall(True)}
        plan = admin.search(q[0]).match(qterms_list[0]).limit(k).plan()
        assert plan.engine == "hybrid", plan.engine
        row["planner_engine"] = plan.engine
        row["explain"] = plan.explain()
        assert "fusion:    score mix" in row["explain"]
        out["cost_curve"].append([arena, row["open"]["fused_ms"]["p50"]])
        out["sizes"][str(n_docs)] = row
        print(f"hybrid: N={n_docs} recall@10 dense="
              f"{row['recall_at_10']['dense']:.3f} hybrid="
              f"{row['recall_at_10']['hybrid']:.3f}  planner engine="
              f"{plan.engine!r}")
    return out


def run_group_sweep(*, iters: int, engine: str = "ref", batch: int = 64,
                    n_docs: int = 50_000, k: int = 5,
                    gs=(1, 2, 4, 8, 16), db=None, ccfg=None) -> dict:
    """Grouped-scan fusion, measured: a B-row batch carrying G distinct
    predicate groups (one per tenant — the paper's query composition
    explosion), answered by the per-group loop (G device programs, each
    streaming the arena: rows_scanned = G*N) vs ONE fused grouped_topk
    program (rows_scanned = N). The G=8 row is the PR's acceptance bar
    (fused >= 3x lower p50) and the point
    `tools/check_bench_regression.py` gates CI on.

    Pass ``db``/``ccfg`` to reuse an already-ingested RagDB (run() does, so
    the full bench builds the 50k corpus once); standalone callers get a
    fresh ``n_docs``-doc arena."""
    if db is None:
        db, _, (ccfg, _) = build_ragdb(CorpusConfig(n_docs=n_docs),
                                       result_cache_size=0)
    n_docs = ccfg.n_docs
    snap = db.log.snapshot()
    arena = snap["emb"].shape[0]
    rng = np.random.default_rng(0)
    q = rng.standard_normal((batch, ccfg.dim)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    min_ts = ccfg.now_ts - 120 * DAY_S
    out = {"batch": batch, "n_docs": n_docs, "arena_rows": arena, "k": k,
           "engine": engine, "sweep": {}}
    for g in gs:
        preds = [Predicate(tenant=i % g, min_ts=min_ts) for i in range(batch)]
        st_loop, st_fused = ExecStats(), ExecStats()
        run_grouped(snap, q, preds, k, engine=engine, stats=st_loop)
        run_grouped_fused(snap, q, preds, k, engine=engine, stats=st_fused)
        t_loop = percentiles(timeit(
            lambda: run_grouped(snap, q, preds, k, engine=engine),
            iters=iters))
        t_fused = percentiles(timeit(
            lambda: run_grouped_fused(snap, q, preds, k, engine=engine),
            iters=iters))
        row = {"groups": g,
               "looped_ms": t_loop, "fused_ms": t_fused,
               "speedup_p50": t_loop["p50"] / max(t_fused["p50"], 1e-9),
               "looped_rows_scanned": st_loop.rows_scanned,
               "fused_rows_scanned": st_fused.rows_scanned,
               "looped_device_calls": st_loop.device_calls,
               "fused_device_calls": st_fused.device_calls}
        assert st_fused.rows_scanned == arena, (
            "fused grouped scan must stream the arena exactly once")
        assert st_loop.rows_scanned == g * arena
        out["sweep"][str(g)] = row
        print(f"group sweep: G={g:3d}  looped p50={t_loop['p50']:7.2f}ms "
              f"({g} scans, {st_loop.rows_scanned} rows)  "
              f"fused p50={t_fused['p50']:7.2f}ms (1 scan, "
              f"{st_fused.rows_scanned} rows)  "
              f"{row['speedup_p50']:4.1f}x")
    return out


def run_paged_section(*, iters: int, n_docs: int = 50_000, batch: int = 64,
                      n_groups: int = 8, k: int = 5, page_rows: int = 1 << 15,
                      engine: str = "ref", db=None, ccfg=None) -> dict:
    """The paged arena-scan regime, measured (ISSUE 7): the SAME fused
    grouped scan, VMEM-resident tiling vs page_rows-sized tiles streamed
    from HBM (double-buffered DMA in the Pallas kernel; the jnp engine
    tiles at the page size). Bits are asserted identical before timing —
    paging changes the memory-traffic schedule, never the results — so the
    only question is overhead: `tools/check_bench_regression.py
    --paged-only` gates paged p50 within 15% of resident at the 50k point.

    Pass ``db``/``ccfg`` to reuse an already-ingested RagDB (run() does);
    standalone callers get a fresh ``n_docs``-doc arena."""
    if db is None:
        db, _, (ccfg, _) = build_ragdb(CorpusConfig(n_docs=n_docs),
                                       result_cache_size=0)
    n_docs = ccfg.n_docs
    snap = db.log.snapshot()
    arena = snap["emb"].shape[0]
    rng = np.random.default_rng(0)
    q = rng.standard_normal((batch, ccfg.dim)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    preds = [Predicate(tenant=i % n_groups, min_ts=ccfg.now_ts - 120 * DAY_S)
             for i in range(batch)]

    st_res, st_pg = ExecStats(), ExecStats()
    s_r, i_r, _ = run_grouped_fused(snap, q, preds, k, engine=engine,
                                    stats=st_res)
    s_p, i_p, _ = run_grouped_fused(snap, q, preds, k, engine=engine,
                                    stats=st_pg, page_rows=page_rows)
    assert (np.asarray(s_r) == np.asarray(s_p)).all(), \
        "paged scan must be bit-identical to resident"
    assert (np.asarray(i_r) == np.asarray(i_p)).all()
    assert st_res.rows_scanned == arena and st_pg.rows_scanned == arena

    t_res = percentiles(timeit(
        lambda: run_grouped_fused(snap, q, preds, k, engine=engine),
        iters=iters))
    t_pg = percentiles(timeit(
        lambda: run_grouped_fused(snap, q, preds, k, engine=engine,
                                  page_rows=page_rows), iters=iters))
    n_pages = -(-arena // page_rows)
    out = {"batch": batch, "n_docs": n_docs, "arena_rows": arena, "k": k,
           "engine": engine, "unique_groups": n_groups,
           "page_rows": page_rows, "n_pages": n_pages,
           "bit_identical": True,
           "resident_ms": t_res, "paged_ms": t_pg,
           "paged_over_resident_p50":
               t_pg["p50"] / max(t_res["p50"], 1e-9)}
    print(f"paged scan: N={arena} rows, {page_rows} rows/page "
          f"-> {n_pages} pages  resident p50={t_res['p50']:7.2f}ms  "
          f"paged p50={t_pg['p50']:7.2f}ms  "
          f"ratio {out['paged_over_resident_p50']:.3f} (bits identical)")
    return out


_SHARDED_K = 10        # k of the sharded lane's (B, k) lists
_SHARDED_BATCH = 8     # one lane-padded query block (B <= 8 pads to 8)


def run_sharded_section(*, iters: int, sizes=(250_000, 1_000_000),
                        shard_counts=(1, 2, 4, 8), devices: int = 8,
                        dim: int = 64) -> dict:
    """The sharded-arena regime, measured (ISSUE 9): p50 of the shard-mapped
    scan at N x S, the collective payload from compiled HLO, merge
    bit-identity against the single-device lexicographic oracle, and the
    per-shard rows audit. Multi-device CPU needs
    --xla_force_host_platform_device_count set BEFORE jax initializes, so
    on CPU this function only ORCHESTRATES: it re-invokes this module in a
    subprocess with --sharded-worker (progress relayed from its stderr) and
    parses the JSON section from its stdout. On a TPU backend the chips are
    real and a chip belongs to one process, so the measurements run here,
    in-process, over ``jax.devices()``."""
    if jax.default_backend() == "tpu":
        n_dev = jax.device_count()
        return _run_sharded_measurements(
            iters=iters, sizes=sizes, devices=n_dev, dim=dim,
            shard_counts=tuple(s for s in shard_counts if s <= n_dev))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    cmd = [sys.executable, "-m", "benchmarks.bench_latency",
           "--sharded-worker", "--iters", str(iters),
           "--devices", str(devices), "--sharded-dim", str(dim),
           "--sizes", *[str(n) for n in sizes],
           "--shards", *[str(s) for s in shard_counts]]
    proc = subprocess.run(cmd, env=env, cwd=root, capture_output=True,
                          text=True, timeout=3600)
    if proc.stderr:
        print(proc.stderr, end="", flush=True)
    if proc.returncode != 0:
        raise RuntimeError("sharded bench worker failed:\n"
                           + proc.stderr[-3000:])
    return json.loads(proc.stdout)


def _run_sharded_measurements(*, iters: int, sizes, shard_counts,
                              devices: int, dim: int,
                              k: int = _SHARDED_K,
                              batch: int = _SHARDED_BATCH) -> dict:
    """Measurement body of the sharded section. Runs INSIDE the
    --sharded-worker subprocess (multi-device jax); prints progress to
    stderr so stdout stays pure JSON for the parent."""
    from repro.core.query import unified_query_ref
    from repro.data.corpus import stream_corpus
    from repro.kernels.arena_scan.sharded import (make_sharded_arena_scan,
                                                  sharded_collective_bytes)
    from repro.launch.mesh import make_mesh

    def say(msg):
        print(msg, file=sys.stderr, flush=True)

    assert jax.device_count() >= max(shard_counts), (
        f"worker sees {jax.device_count()} devices, "
        f"needs {max(shard_counts)}")
    out = {"k": k, "dim": dim, "batch": batch, "devices": devices,
           "placement": "hash", "shard_counts": list(shard_counts),
           "sizes": {}, "cost_curve": []}
    for n in sizes:
        ccfg = CorpusConfig(n_docs=n, dim=dim)
        cols = {"emb": np.empty((n, dim), np.float32),
                "tenant": np.empty(n, np.int32),
                "category": np.empty(n, np.int32),
                "updated_at": np.empty(n, np.int32),
                "acl": np.empty(n, np.uint32),
                "doc_id": np.empty(n, np.int32)}
        i = 0
        for ch in stream_corpus(ccfg):
            m = int(ch.emb.shape[0])
            for name, arr in cols.items():
                arr[i:i + m] = np.asarray(getattr(ch, name))
            i += m
        say(f"sharded: N={n} corpus streamed in {-(-n // 65_536)} chunks "
            f"(host holds one chunk + the arena columns)")
        qj = jnp.asarray(make_queries(ccfg, 1, batch=batch, seed=11)[0])
        pred = jnp.asarray(Predicate().as_array())
        row = {"arena_rows": n, "arena_bytes": n * dim * 4, "shards": {}}
        s1_p50 = None
        for S in shard_counts:
            rps = n // S
            # hash placement realized directly: doc d owns slot
            # (d % S) * rps + d // S — region r is slots [r*rps, (r+1)*rps)
            order = np.concatenate([np.arange(r, n, S) for r in range(S)])
            store = {name: jnp.asarray(arr[order])
                     for name, arr in cols.items()}
            store["version"] = jnp.zeros(n, jnp.int32)
            store["commit_ts"] = jnp.int32(1)
            store["n_live"] = jnp.int32(n)
            mesh = make_mesh((S,), ("data",))
            raw = make_sharded_arena_scan(mesh, ("data",), n, k)
            fn = jax.jit(raw)
            s, sl, rows = fn(store, qj, pred)
            s0, i0 = unified_query_ref(store, qj, pred, k)
            doc_col = cols["doc_id"][order]
            ids = np.where(np.asarray(sl) >= 0, doc_col[np.asarray(sl)], -1)
            ids0 = np.where(np.asarray(i0) >= 0, doc_col[np.asarray(i0)], -1)
            bit_identical = bool(
                np.array_equal(np.asarray(s), np.asarray(s0))
                and np.array_equal(ids, ids0))
            recall = float((ids == ids0).mean())
            cbytes = int(sharded_collective_bytes(raw, store, qj, pred))
            t = percentiles(timeit(lambda: fn(store, qj, pred), iters=iters))
            if S == shard_counts[0]:
                s1_p50 = t["p50"]
            cell = {"scan_ms": t, "collective_bytes": cbytes,
                    "payload_bound_bytes": 2 * S * batch * k * 8,
                    "collective_frac_of_arena": cbytes / row["arena_bytes"],
                    "shard_rows_scanned": np.asarray(rows).tolist(),
                    "bit_identical": bit_identical, "recall_at_k": recall,
                    "speedup_vs_s1_p50": (s1_p50 / max(t["p50"], 1e-9)
                                          if s1_p50 is not None else None)}
            row["shards"][str(S)] = cell
            say(f"sharded: N={n:8d} S={S}  p50={t['p50']:8.2f}ms  "
                f"collective={cbytes}B (bound {cell['payload_bound_bytes']}B"
                f", {cell['collective_frac_of_arena']:.2e} of arena)  "
                f"rows/shard={rps}  bit_identical={bit_identical}")
            del store, fn, raw
        out["cost_curve"].append(
            [n, row["shards"][str(shard_counts[-1])]["scan_ms"]["p50"]])
        out["sizes"][str(n)] = row
        del cols
    return out


def run_ivf_curves(*, iters: int, k: int = 10, n_queries: int = 32,
                   sizes=(5_000, 20_000, 50_000),
                   nprobes=(2, 4, 8, 16)) -> dict:
    """The sub-linear route, measured: p50 vs nprobe at several corpus sizes
    with recall@10 against the exact ref scan over the same session path,
    plus the planner's own choice for an unconstrained predicate group.

    The default-nprobe points become the planner's "ivf" cost curve — and
    the 50k row records the PR's acceptance bar: planner picks ivf, p50
    >= 3x faster than exact at recall@10 >= 0.95, candidate rows < 25% of
    the arena."""
    out = {"k": k, "n_queries": n_queries, "sizes": {}, "cost_curve": []}
    for n_docs in sizes:
        db, _, (ccfg, scfg) = build_ragdb(CorpusConfig(n_docs=n_docs),
                                          result_cache_size=0)
        index = db.build_index()
        admin = db.admin_session()
        arena = scfg.capacity
        qs = [np.asarray(q)[0] for q in make_queries(ccfg, n_queries, batch=1,
                                                     seed=3)]
        exact = [admin.search(q).limit(k).using("ref").run().slots[0]
                 for q in qs]
        qi = [0]

        def ref_call():
            admin.search(qs[qi[0] % n_queries]).limit(k).using("ref").run()
            qi[0] += 1

        p50_ref = percentiles(timeit(ref_call, iters=iters))["p50"]
        plan = admin.search(qs[0]).limit(k).plan()
        row = {"arena_rows": arena, "n_docs": n_docs,
               "index": {"n_clusters": index.n_clusters,
                         "cluster_cap": index.cluster_cap,
                         "overflow": len(index.overflow)},
               "ref_p50_ms": p50_ref, "nprobe": {},
               "planner_engine": plan.engine,
               "planner_reason": plan.engine_reason,
               "explain": plan.explain()}
        base_cfg = db.planner_cfg
        for nprobe in nprobes:
            db.planner_cfg = dataclasses.replace(base_cfg, ivf_nprobe=nprobe)
            hits = 0
            rows0 = db.stats.rows_scanned
            for i, q in enumerate(qs):
                res = admin.search(q).limit(k).using("ivf").run()
                hits += len(set(res.slots[0].tolist())
                            & set(exact[i].tolist()))
            recall = hits / (k * n_queries)
            cand_frac = (db.stats.rows_scanned - rows0) / (n_queries * arena)
            qi[0] = 0

            def ivf_call():
                admin.search(qs[qi[0] % n_queries]).limit(k).using("ivf").run()
                qi[0] += 1

            p50 = percentiles(timeit(ivf_call, iters=iters))["p50"]
            row["nprobe"][nprobe] = {
                "p50_ms": p50, "recall_at_10": recall,
                "candidate_frac_of_arena": cand_frac,
                "speedup_vs_ref_p50": p50_ref / max(p50, 1e-9)}
            print(f"ivf: N={n_docs:6d} nprobe={nprobe:3d}  "
                  f"p50={p50:6.2f}ms (ref {p50_ref:6.2f}ms, "
                  f"{p50_ref / max(p50, 1e-9):4.1f}x)  recall@10={recall:.3f}  "
                  f"scan={cand_frac:5.1%} of arena")
        db.planner_cfg = base_cfg
        # the cost-model point is measured RAW (probe + fused scan on the
        # snapshot), matching how run_engine_curves times the other engines
        # — mixing session-path and device-call timings in one CostModel
        # would bias the planner near the crossover
        snap = db.log.snapshot()
        pred = Predicate()
        qi[0] = 0

        def raw_ivf():
            s, _ = ivf_query(snap, index, jnp.asarray(qs[qi[0] % n_queries][None, :]),
                             pred, k, nprobe=index.cfg.nprobe)
            jax.block_until_ready(s)
            qi[0] += 1

        raw_p50 = percentiles(timeit(raw_ivf, iters=iters))["p50"]
        row["raw_p50_ms"] = raw_p50
        out["cost_curve"].append([arena, raw_p50])
        out["sizes"][str(n_docs)] = row
        print(f"ivf: N={n_docs} planner chose {plan.engine!r} "
              f"({plan.engine_reason})")
    return out


def run_engine_curves(ccfg, *, iters: int, k: int,
                      warm_probe_ms: float | None = None,
                      capacities=(1 << 10, 1 << 12, 1 << 14)) -> dict:
    """Measure each runnable engine's p50 at several arena sizes and save the
    curves in `CostModel.from_bench` format — the planner's measured cost
    model is literally this section fed back in."""
    engines = ["ref"]
    if jax.default_backend() == "tpu":
        engines.append("pallas")
    curves: dict[str, list[list[float]]] = {e: [] for e in engines}
    for cap in capacities:
        sub = CorpusConfig(n_docs=cap // 2, dim=ccfg.dim)
        db = RagDB(StoreConfig(capacity=cap, dim=ccfg.dim))
        db.ingest(make_corpus(sub))
        snap = db.log.snapshot()
        qs = [np.asarray(q, np.float32) for q in make_queries(sub, 8, batch=1)]
        pred = Predicate(min_ts=sub.now_ts - 120 * DAY_S)
        for eng in engines:
            qi = [0]

            def go():
                s, _ = unified_query(snap, jnp.asarray(qs[qi[0] % len(qs)]),
                                     pred, k, engine=eng)
                jax.block_until_ready(s)
                qi[0] += 1

            p50 = percentiles(timeit(go, iters=iters))["p50"]
            curves[eng].append([cap, p50])
            print(f"engine curve: {eng:6s} n_rows={cap:6d}  p50={p50:.3f}ms")
    return {"engines": curves, "warm_probe_ms": warm_probe_ms}


def run_adaptive_serving(*, iters: int, engine: str, k: int, batch: int = 32,
                         n_groups: int = 4, n_docs: int = 20_000,
                         dim: int = 128) -> dict:
    """The serve fast path end to end through `db.execute` at B=32/G=4 on a
    20k-doc arena (the PR-1 headline config): grouped+bucketed with the
    result cache bypassed (cold), vs all-hit (cached), plus a cold
    varying-batch-size stream isolating the recompilation overhead that
    bucketing removes."""
    ccfg = CorpusConfig(n_docs=n_docs, dim=dim)
    db = RagDB(StoreConfig(capacity=1 << (int(np.ceil(np.log2(n_docs))) + 1),
                           dim=dim))
    db.ingest(make_corpus(ccfg))
    rng = np.random.default_rng(0)
    min_ts = ccfg.now_ts - 120 * DAY_S
    sessions = [db.session(Principal(tenant_id=i % n_groups,
                                     group_bits=0xFFFFFFFF))
                for i in range(batch)]

    def plans_for(qmat):
        return [sessions[i].search(qmat[i], normalize=False)
                .newer_than(min_ts).limit(k).using(engine).plan()
                for i in range(batch)]

    def norm(qmat):
        return qmat / np.linalg.norm(qmat, axis=1, keepdims=True)

    fixed = plans_for(norm(rng.standard_normal((batch, dim)).astype(np.float32)))
    # cold: cache bypassed — grouped + bucketed device execution every time
    t_cold = percentiles(timeit(lambda: db.execute(fixed, use_cache=False),
                                iters=iters))
    # cached: identical plans against an unchanged snapshot — all hits
    t_hit = percentiles(timeit(lambda: db.execute(fixed), iters=iters))
    # miss-path cost including key hashing: a fresh batch every iteration
    fresh = [plans_for(norm(rng.standard_normal((batch, dim)).astype(np.float32)))
             for _ in range(iters + 5)]
    fi = [0]

    def miss():
        db.execute(fresh[fi[0] % len(fresh)])
        fi[0] += 1

    t_miss = percentiles(timeit(miss, iters=iters))

    out = {"batch": batch, "unique_groups": n_groups, "n_docs": n_docs,
           "grouped_cold_ms": t_cold, "cached_ms": t_hit,
           "cache_miss_ms": t_miss,
           "cache_speedup_p50": t_miss["p50"] / max(t_hit["p50"], 1e-9),
           "recompile_stream": run_recompile_stream(db),
           "shape_cache": {"hits": db.shapes.hits, "misses": db.shapes.misses},
           "db_explain": db.explain()}
    print(f"adaptive serving: B={batch} G={n_groups}  "
          f"cold p50={t_cold['p50']:.2f}ms  miss p50={t_miss['p50']:.2f}ms  "
          f"cache-hit p50={t_hit['p50']:.3f}ms  "
          f"({out['cache_speedup_p50']:.0f}x hit-vs-cold)")
    return out


def run_recompile_stream(db, *, k: int = 7,
                         sizes=(33, 35, 37, 39, 41, 43, 45, 47)) -> dict:
    """One cold pass over a stream of distinct batch sizes, exact shapes vs
    bucketed. Exact shapes compile one program per size; bucketed pads every
    size to one bucket (64) and compiles once. k=7 keeps these programs
    disjoint from every other section's, so both variants start cold."""
    rng = np.random.default_rng(1)
    snap = db.log.snapshot()
    dim = snap["emb"].shape[1]
    pred = Predicate(tenant=0)
    batches = [rng.standard_normal((b, dim)).astype(np.float32) for b in sizes]

    def one_pass(shapes):
        t0 = time.perf_counter()
        for q in batches:
            run_grouped(snap, q, [pred] * q.shape[0], k, shapes=shapes)
        return time.perf_counter() - t0

    bucketed_first = one_pass(CompiledShapes())      # compiles bucket 64 once
    exact_first = one_pass(None)                     # compiles all 8 sizes
    # steady state: everything above is compiled now
    t_exact = percentiles(timeit(lambda: one_pass(None), iters=10))
    t_bucket = percentiles(timeit(lambda: one_pass(CompiledShapes()), iters=10))
    out = {"sizes": list(sizes), "k": k,
           "exact_first_pass_s": exact_first,
           "bucketed_first_pass_s": bucketed_first,
           "exact_steady_p50_ms": t_exact["p50"],
           "bucketed_steady_p50_ms": t_bucket["p50"],
           "first_pass_speedup": exact_first / max(bucketed_first, 1e-9)}
    print(f"recompile stream ({len(sizes)} distinct batch sizes): "
          f"exact first pass {exact_first * 1e3:.0f}ms "
          f"(one compile per size), bucketed {bucketed_first * 1e3:.0f}ms "
          f"(one compile total)  {out['first_pass_speedup']:.1f}x")
    return out


def run_batched_vs_looped(db, ccfg, *, iters: int, engine: str, k: int,
                          batch: int = 32, n_groups: int = 4) -> dict:
    """The RAGEngine.serve hot path, isolated: B per-request predicates with
    G unique groups — looped (B device calls, the pre-front-door serve loop)
    vs predicate-group batched (G device calls over stacked rows)."""
    rng = np.random.default_rng(0)
    q = rng.standard_normal((batch, ccfg.dim)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    preds = [Predicate(tenant=i % n_groups,
                       min_ts=ccfg.now_ts - 120 * DAY_S)
             for i in range(batch)]
    snap = db.log.snapshot()

    def looped():
        for i, p in enumerate(preds):
            s, _ = unified_query(snap, jnp.asarray(q[i:i + 1]), p, k,
                                 engine=engine)
            jax.block_until_ready(s)

    def grouped():
        run_grouped(snap, q, preds, k, engine=engine)

    t_loop = percentiles(timeit(looped, iters=iters))
    t_group = percentiles(timeit(grouped, iters=iters))
    out = {"batch": batch, "unique_groups": n_groups,
           "looped_ms": t_loop, "grouped_ms": t_group,
           "speedup_p50": t_loop["p50"] / max(t_group["p50"], 1e-9)}
    print(f"batched retrieval: B={batch} requests, G={n_groups} groups  "
          f"looped p50={t_loop['p50']:.2f}ms ({batch} calls)  "
          f"grouped p50={t_group['p50']:.2f}ms ({n_groups} calls)  "
          f"speedup {out['speedup_p50']:.1f}x")
    return out


def _main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--gsweep-only", action="store_true",
                    help="run only the group_sweep section (CI regression "
                         "gate); writes {'group_sweep': ...} to --out")
    ap.add_argument("--hybrid-only", action="store_true",
                    help="run only the hybrid section (CI regression "
                         "gate); writes {'hybrid': ...} to --out")
    ap.add_argument("--paged-only", action="store_true",
                    help="run only the paged_scan section (CI regression "
                         "gate); writes {'paged_scan': ...} to --out")
    ap.add_argument("--sharded-only", action="store_true",
                    help="run only the sharded section (CI regression "
                         "gate; spawns one multi-device subprocess); "
                         "writes {'sharded': ...} to --out")
    ap.add_argument("--sharded-worker", action="store_true",
                    help=argparse.SUPPRESS)   # internal: the subprocess body
    ap.add_argument("--page-rows", type=int, default=1 << 15,
                    help="with --paged-only: rows per page tile")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--gs", type=int, nargs="+", default=None,
                    help="with --gsweep-only: group counts to measure "
                         "(default 1 2 4 8 16; CI gates on 8 alone)")
    ap.add_argument("--sizes", type=int, nargs="+", default=None,
                    help="with --hybrid-only/--sharded-only: corpus sizes "
                         "to measure (hybrid default 50000 — the gated "
                         "point; sharded default 250000 1000000, CI uses "
                         "250000 alone)")
    ap.add_argument("--shards", type=int, nargs="+", default=None,
                    help="with --sharded-only: shard counts to measure "
                         "(default 1 2 4 8)")
    ap.add_argument("--devices", type=int, default=8,
                    help="with --sharded-only: emulated host device count "
                         "for the worker subprocess (default 8)")
    ap.add_argument("--sharded-dim", type=int, default=64,
                    help="with --sharded-only: embedding dim of the "
                         "streamed bench corpus (default 64)")
    ap.add_argument("--out", default=None,
                    help="with --gsweep-only/--hybrid-only/--sharded-only: "
                         "output JSON path (default "
                         "results/bench_latency.json is NOT touched)")
    args = ap.parse_args()
    if args.sharded_worker:
        section = _run_sharded_measurements(
            iters=args.iters or 10,
            sizes=tuple(args.sizes) if args.sizes else (250_000, 1_000_000),
            shard_counts=tuple(args.shards) if args.shards else (1, 2, 4, 8),
            devices=args.devices, dim=args.sharded_dim)
        print(json.dumps(section))
        return
    if args.sharded_only:
        section = run_sharded_section(
            iters=args.iters or 10,
            sizes=tuple(args.sizes) if args.sizes else (250_000, 1_000_000),
            shard_counts=tuple(args.shards) if args.shards else (1, 2, 4, 8),
            devices=args.devices, dim=args.sharded_dim)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"sharded": section}, f, indent=1)
            print(f"wrote {args.out}")
        return
    if args.gsweep_only:
        sweep = run_group_sweep(iters=args.iters or 20,
                                gs=tuple(args.gs) if args.gs else
                                (1, 2, 4, 8, 16))
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"group_sweep": sweep}, f, indent=1)
            print(f"wrote {args.out}")
        return
    if args.hybrid_only:
        section = run_hybrid_section(
            iters=args.iters or 20,
            sizes=tuple(args.sizes) if args.sizes else (50_000,))
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"hybrid": section}, f, indent=1)
            print(f"wrote {args.out}")
        return
    if args.paged_only:
        section = run_paged_section(iters=args.iters or 20,
                                    page_rows=args.page_rows)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"paged_scan": section}, f, indent=1)
            print(f"wrote {args.out}")
        return
    run(**({"iters": args.iters} if args.iters else {}))


if __name__ == "__main__":
    _main()
