"""The profiler trace of a traced run, reduced to what the readers need.

`Profile` holds the device operations (name, program, start, duration)
of the chips the run used and the host annotations the benchmark wrote
around its calls into each layer (``bench.*``), all on the profiler's
clock. Busy time is the union of the device operation intervals inside
the ``bench.window`` annotation; each idle gap is put down to the host
annotation that covers most of it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

import jax

WINDOW = "bench.window"


@dataclasses.dataclass
class Op:
    name: str          # HLO instruction name, e.g. "_run.1", "fusion.3"
    module: str        # the program it ran in, e.g. "jit__run"
    t0: float          # seconds, profiler clock
    dur: float
    kernel: bool = False    # a Pallas (Mosaic) custom call


@dataclasses.dataclass
class Profile:
    ops: list                      # Op on the device planes, in time order
    notes: list                    # (name, t0, t1) host annotations
    n_devices: int

    @property
    def window(self) -> tuple[float, float] | None:
        spans = [(a, b) for n, a, b in self.notes if n == WINDOW]
        return (min(a for a, _ in spans), max(b for _, b in spans)) \
            if spans else None

    def in_window(self) -> list:
        w = self.window
        if w is None:
            return []
        return [o for o in self.ops if o.t0 >= w[0] and o.t0 + o.dur <= w[1]]

    def busy_intervals(self) -> list:
        """Union of the device operation intervals inside the window."""
        out: list = []
        for o in sorted(self.in_window(), key=lambda o: o.t0):
            a, b = o.t0, o.t0 + o.dur
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices the run used."""
        return sum(b - a for a, b in self.busy_intervals()) / max(
            self.n_devices, 1)

    def window_s(self) -> float:
        w = self.window
        return 0.0 if w is None else w[1] - w[0]

    def idle_gaps(self) -> dict:
        """Idle seconds inside the window, by the host annotation that
        overlaps each gap most (``host.other`` where none does)."""
        w = self.window
        if w is None:
            return {}
        edges = [w[0]]
        for a, b in self.busy_intervals():
            edges += [a, b]
        edges.append(w[1])
        notes = [(n, a, b) for n, a, b in self.notes if n != WINDOW]
        out: dict = defaultdict(float)
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            best, best_ov = "host.other", 0.0
            for n, na, nb in notes:
                ov = min(b, nb) - max(a, na)
                if ov > best_ov:
                    best, best_ov = n, ov
            out[best] += b - a
        return dict(out)

    def op_seconds(self) -> dict:
        """Device seconds inside the window per `module/op` name."""
        out: dict = defaultdict(float)
        for o in self.in_window():
            out[f"{o.module}/{o.name}" if o.module else o.name] += o.dur
        return dict(out)


def start(log_dir: str) -> None:
    """Start the profiler: device and host tracers on, Python tracer off
    (it would record every Python call of the serving loop)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


#: The chips' planes (other "/device:" planes, such as a custom
#: interconnect trace, are not chips).
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")


def _short(hlo_text: str) -> str:
    """``%_run.1 = (...) custom-call(...)`` -> ``_run.1``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


def _device_ops(plane) -> list:
    """The plane's operations, each put in the program whose execution on
    the ``XLA Modules`` line holds its start."""
    lines = {ln.name: ln for ln in plane.lines}
    mods = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns,
                    _MODULE.match(ev.name).group(1))
                   for ev in (lines["XLA Modules"].events
                              if "XLA Modules" in lines else ())))
    starts = [m[0] for m in mods]
    ops = []
    for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
        j = bisect.bisect_right(starts, ev.start_ns) - 1
        module = (mods[j][2] if j >= 0 and ev.start_ns < mods[j][1]
                  else str(_stat(ev, "hlo_module") or ""))
        ops.append(Op(_short(ev.name), module, ev.start_ns * 1e-9,
                      ev.duration_ns * 1e-9,
                      kernel='custom_call_target="tpu_custom_call"'
                      in ev.name))
    return ops


def _newest(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    return paths[-1]


def load(log_dir: str) -> Profile:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(_newest(log_dir))
    ops, notes, n_dev = [], [], 0
    planes = list(data.planes)
    # without an accelerator (the harness's own tests) the host plane's XLA
    # executor threads stand in for the device
    on_host = not any(DEVICE_PLANE.match(p.name) for p in planes)
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            n_dev += 1
            ops += _device_ops(plane)
        elif plane.name.startswith("/host:"):
            n_dev += int(on_host and plane.name == "/host:CPU")
            for line in plane.lines:
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    if ev.name.startswith("bench."):
                        notes.append((ev.name, a, a + ev.duration_ns * 1e-9))
                    elif on_host and ev.duration_ns > 0 and (
                            module := _stat(ev, "hlo_module")):
                        ops.append(Op(ev.name, str(module), a,
                                      ev.duration_ns * 1e-9,
                                      kernel=ev.name.startswith("custom")))
    ops.sort(key=lambda o: o.t0)
    return Profile(ops=ops, notes=notes, n_devices=n_dev)
