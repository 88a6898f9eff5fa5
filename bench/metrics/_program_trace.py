"""The program's own spans on the profiler's clock.

While the profiler runs, an enabled `repro.obs.Tracer` writes each span as
a host event named ``rag.<span>`` whose stats are the span's scalar
annotations (`repro.obs.tracer`): ``unit`` numbers a dispatch unit, and a
``launch`` carries the shape it launched (``family``, ``rows``,
``bucket`` and, for the arena-scan kernel, ``passes``). They are read
from the same ``.xplane.pb`` that `RunRecord.profile` was loaded from,
which is still on disk while the readers run, and parsed once per run. A
program that writes no such events (one older than them, or a run without
a profile) reads as no events, never as an error.
"""
from __future__ import annotations

import dataclasses
import weakref

from bench.metrics._scan_cost import scan_ops

#: Launch families whose scans compile as ``jit__run`` (the programs
#: `_scan_cost.scan_ops` reads): the exact Pallas engine on one predicate
#: group (filtered) or fused over several (grouped), and hybrid.
SCAN_FAMILIES = ("filtered", "grouped", "hybrid")


@dataclasses.dataclass
class Event:
    name: str          # the span's name, without "rag."
    t0: float          # seconds, profiler clock
    t1: float
    stats: dict


_parsed: tuple = (None, [])       # (weakref to the run, its events)


def _load(run) -> list:
    """Every ``rag.*`` host event of the newest trace under the harness's
    trace directory, in start order."""
    from jax.profiler import ProfileData

    from bench.harness import TRACE_DIR
    from bench.trace import _newest
    try:
        path = _newest(TRACE_DIR)
    except FileNotFoundError:
        return []
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("rag."):
                    a = ev.start_ns * 1e-9
                    out.append(Event(ev.name[4:], a,
                                     a + ev.duration_ns * 1e-9,
                                     {k: v for k, v in ev.stats}))
    out.sort(key=lambda e: e.t0)
    return out


def events(run) -> list:
    """The run's ``rag.*`` events that lie inside the traced window."""
    global _parsed
    if run.profile is None or run.profile.window is None:
        return []
    ref, evs = _parsed
    if ref is None or ref() is not run:
        evs = _load(run)
        _parsed = (weakref.ref(run), evs)
    a, b = run.profile.window
    return [e for e in evs if e.t0 >= a and e.t1 <= b]


def scan_launches(run) -> list:
    """The window's ``launch`` events of the ``jit__run`` families, in
    dispatch order."""
    return [e for e in events(run) if e.name == "launch"
            and e.stats.get("family") in SCAN_FAMILIES]


def paired(run) -> list | None:
    """Each scan launch with its scan operation on the chip, both in
    dispatch order; None where there is nothing to pair, or where the
    launches and the operations do not pair one to one."""
    launches, ops = scan_launches(run), scan_ops(run)
    if not launches or len(launches) != len(ops):
        return None
    return list(zip(launches, sorted(ops, key=lambda o: o.t0)))


def launch_spans(run) -> list:
    """One ``launch`` span per dispatch unit from the run's traces (a unit
    fans its span into every member trace)."""
    seen: dict = {}
    for tr in run.spans:
        for s in tr.spans:
            if s.name == "launch" and s.t1 is not None:
                seen.setdefault(s.ann.get("unit", (s.t0, s.t1)), s)
    return list(seen.values())
