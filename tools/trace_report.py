#!/usr/bin/env python
"""Offline flight-recorder analysis: slowest traces, stage/engine rollups,
and the cost-model calibration audit — from a recorder dump, no repo state.

Usage:
    python tools/trace_report.py DUMP.json [--top 5] [--stage-pcts]
    python tools/trace_report.py DUMP.json --perfetto OUT.json

DUMP.json is a `FlightRecorder.dump()` file (schema
``repro.obs.flight_recorder/v1``) — e.g. results/flight_recorder_chaos.json
written by ``bench_serving --chaos``. The report:

  1. header: recorded/retained/pinned counts + pin-reason histogram (what
     fraction of retained traces are there because something went wrong);
  2. top-N slowest retained traces with their full span breakdown — the
     "why was THIS request slow" view (queue wait vs degrade vs device sync
     vs warm probe is visible per request, annotations inline);
  3. per-stage rollup across every retained trace (count/mean/p95/max per
     span name) and per-engine / per-tenant trace rollups;
  4. if the dump embeds a `CalibrationTable.snapshot()`: the predicted-vs-
     measured audit — per-engine drift ratio and the worst (engine,N,G,k)
     buckets by absolute regret (|measured - predicted| x count), i.e.
     where the planner's price list is most wrong and `CostModel.
     calibrated()` would move decisions.

``--perfetto`` instead converts the dump to a Chrome/Perfetto
``trace_event`` JSON (one pseudo-thread per trace, ``ph: "X"`` complete
events) loadable at https://ui.perfetto.dev — the dump stores raw
`perf_counter` span times, so the conversion normalizes to the earliest
span exactly like `FlightRecorder.dump_perfetto`.

Exit 0 on success, 2 on malformed/missing input.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

SCHEMA = "repro.obs.flight_recorder/v1"


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, ValueError) as e:
        print(f"error: cannot read {path}: {e}", file=sys.stderr)
        sys.exit(2)
    if d.get("schema") != SCHEMA:
        print(f"error: {path} is not a flight-recorder dump "
              f"(schema={d.get('schema')!r}, want {SCHEMA!r})",
              file=sys.stderr)
        sys.exit(2)
    return d


def _pct(vals: list[float], q: float) -> float:
    """Nearest-rank percentile (no numpy: the report must open anywhere)."""
    if not vals:
        return 0.0
    v = sorted(vals)
    idx = min(len(v) - 1, max(0, int(round(q / 100.0 * (len(v) - 1)))))
    return v[idx]


def _root(trace: dict) -> dict:
    return trace["spans"][0]


def _fmt_ann(ann: dict, skip=("req_id",)) -> str:
    parts = [f"{k}={v}" for k, v in ann.items() if k not in skip]
    return (" [" + " ".join(parts) + "]") if parts else ""


def _span_tree_lines(trace: dict) -> list[str]:
    """Indented per-span lines, children under parents, durations inline."""
    by_parent: dict[int, list[dict]] = {}
    for s in trace["spans"]:
        by_parent.setdefault(s["parent_id"], []).append(s)
    lines: list[str] = []

    def walk(span: dict, depth: int) -> None:
        dur = span["dur_ms"]
        dur_s = f"{dur:8.2f}ms" if dur is not None else "    open  "
        lines.append(f"      {'  ' * depth}{span['name']:<24s}{dur_s}"
                     f"{_fmt_ann(span['ann'])}")
        for child in by_parent.get(span["span_id"], []):
            walk(child, depth + 1)

    walk(_root(trace), 0)
    return lines


def report(dump: dict, top: int, stage_pcts: bool) -> None:
    traces = dump["traces"]
    pin_hist: dict[str, int] = {}
    for t in traces:
        for p in t["pins"]:
            pin_hist[p] = pin_hist.get(p, 0) + 1
    print(f"flight recorder: {dump['recorded']} recorded, "
          f"{len(traces)} retained (ring cap {dump['cap']}, "
          f"{len(dump['pinned'])} pinned / cap {dump['pin_cap']}, "
          f"{dump['pin_drops']} pin drops)")
    if pin_hist:
        print("  pin reasons: " + ", ".join(
            f"{k}={v}" for k, v in sorted(pin_hist.items())))

    # -- slowest traces, full span tree each ------------------------------
    ranked = sorted((t for t in traces if t["duration_ms"] is not None),
                    key=lambda t: -t["duration_ms"])
    print(f"\ntop {min(top, len(ranked))} slowest retained traces:")
    for t in ranked[:top]:
        root = _root(t)
        pins = (" pins=[" + ",".join(t["pins"]) + "]") if t["pins"] else ""
        print(f"  {t['trace_id']} req={root['ann'].get('req_id')} "
              f"{t['duration_ms']:.2f}ms{pins}")
        for line in _span_tree_lines(t):
            print(line)

    # -- per-stage rollup --------------------------------------------------
    stages: dict[str, list[float]] = {}
    for t in traces:
        for s in t["spans"]:
            if s["dur_ms"] is not None:
                stages.setdefault(s["name"], []).append(s["dur_ms"])
    print("\nper-stage rollup (closed spans across retained traces):")
    for name, vals in sorted(stages.items(),
                             key=lambda kv: -sum(kv[1])):
        row = (f"  {name:<16s} n={len(vals):4d}  "
               f"mean={sum(vals) / len(vals):8.3f}ms  "
               f"max={max(vals):8.2f}ms")
        if stage_pcts:
            row += (f"  p50={_pct(vals, 50):8.3f}ms"
                    f"  p95={_pct(vals, 95):8.2f}ms")
        print(row)

    # -- per-engine / per-tenant trace rollups -----------------------------
    def rollup(key: str) -> dict[str, list[float]]:
        out: dict[str, list[float]] = {}
        for t in traces:
            if t["duration_ms"] is None:
                continue
            val = _root(t)["ann"].get(key)
            if val is None:         # scheduler traces carry engine on the
                for s in t["spans"]:   # degrade span, not the root
                    if key in s["ann"]:
                        val = s["ann"][key]
                        break
            if val is not None:
                out.setdefault(str(val), []).append(t["duration_ms"])
        return out

    for key in ("engine", "tenant"):
        r = rollup(key)
        if not r:
            continue
        print(f"\nper-{key} trace durations:")
        for val, durs in sorted(r.items()):
            print(f"  {key}={val:<10s} n={len(durs):4d}  "
                  f"mean={sum(durs) / len(durs):8.2f}ms  "
                  f"p95={_pct(durs, 95):8.2f}ms  max={max(durs):8.2f}ms")

    # -- calibration audit -------------------------------------------------
    cal = dump.get("calibration")
    if not cal:
        return
    print(f"\ncost-model calibration ({cal['recorded']} unit samples):")
    for eng, e in sorted(cal.get("engines", {}).items()):
        ratio = e.get("ratio")
        r_s = f"x{ratio:.2f}" if ratio is not None else "unpriced"
        print(f"  {eng:<8s} {e['count']:5d} units over {e['buckets']:3d} "
              f"buckets  measured/predicted {r_s}")
    # worst buckets by absolute regret: total measured-minus-predicted ms
    # (signed magnitude — both over- and under-prediction move the planner)
    rows = []
    for key, u in cal.get("units", {}).items():
        if u.get("ratio") is None:
            continue
        regret = u["priced_device_ms"] - u["predicted_ms"]
        rows.append((abs(regret), regret, key, u))
    rows.sort(reverse=True)
    if rows:
        print("  worst buckets by |measured - predicted| total:")
        for _, regret, key, u in rows[:8]:
            print(f"    {key:<34s} n={u['count']:4d}  "
                  f"predicted {u['predicted_ms']:8.2f}ms  "
                  f"measured {u['priced_device_ms']:8.2f}ms  "
                  f"regret {regret:+8.2f}ms (x{u['ratio']:.2f})")
    e2e = cal.get("e2e", {})
    if e2e:
        print("  end-to-end (scheduler-fed, includes queue + pipelining):")
        for key, d in sorted(e2e.items()):
            print(f"    {key:<28s} n={d['count']:4d}  "
                  f"mean={d['mean_ms']:8.2f}ms  max={d['max_ms']:8.2f}ms")


def to_perfetto(dump: dict) -> dict:
    """Rebuild the Chrome ``trace_event`` view from dumped span dicts —
    the same normalization `FlightRecorder.dump_perfetto` applies live."""
    traces = dump["traces"]
    t_base = min((s["t0"] for t in traces for s in t["spans"]), default=0.0)
    events: list[dict] = []
    for tid, t in enumerate(traces):
        root = _root(t)
        label = t["trace_id"]
        if root["ann"].get("req_id") is not None:
            label += f" req={root['ann']['req_id']}"
        if t["pins"]:
            label += " [" + ",".join(t["pins"]) + "]"
        events.append({"ph": "M", "name": "thread_name", "pid": 1,
                       "tid": tid, "args": {"name": label}})
        for s in t["spans"]:
            if s["t1"] is None:
                continue
            events.append({"name": s["name"], "cat": "serve", "ph": "X",
                           "ts": (s["t0"] - t_base) * 1e6,
                           "dur": (s["t1"] - s["t0"]) * 1e6,
                           "pid": 1, "tid": tid,
                           "args": {"span_id": s["span_id"],
                                    "parent_id": s["parent_id"],
                                    **s["ann"]}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dump", help="FlightRecorder.dump() JSON "
                    "(e.g. results/flight_recorder_chaos.json)")
    ap.add_argument("--top", type=int, default=5,
                    help="slowest traces to print with full span trees "
                         "(default 5)")
    ap.add_argument("--stage-pcts", action="store_true",
                    help="add p50/p95 columns to the per-stage rollup")
    ap.add_argument("--perfetto", metavar="OUT",
                    help="write a Chrome/Perfetto trace_event JSON instead "
                         "of printing the report")
    args = ap.parse_args(argv)
    dump = _load(args.dump)
    if args.perfetto:
        d = to_perfetto(dump)
        with open(args.perfetto, "w") as f:
            json.dump(d, f, indent=1)
        print(f"wrote {args.perfetto} ({len(d['traceEvents'])} events from "
              f"{len(dump['traces'])} traces) — open at "
              f"https://ui.perfetto.dev")
        return 0
    print(f"{os.path.basename(args.dump)}:")
    report(dump, args.top, args.stage_pcts)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
