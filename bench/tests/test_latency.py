"""Read latency runs from each read's due time: a stall in the serving loop
shows in every read that fell due during it."""
import time

import numpy as np

from bench import harness
from repro.serving import scheduler as sched_mod

STALL_S = 0.3


def test_due_time_latency_counts_a_stall(tiny, monkeypatch):
    cell = tiny("dense768.levels", rate=80.0)
    system = harness.build(cell, 7, 1.0, say=lambda *_: None)
    harness.warm(system, say=lambda *_: None)
    step = sched_mod.Scheduler.step
    stalled = []

    def stalling_step(self):
        if not stalled:
            stalled.append(time.monotonic())
            time.sleep(STALL_S)
        return step(self)

    monkeypatch.setattr(sched_mod.Scheduler, "step", stalling_step)
    win = harness.drive(system, 1.0, max_batch=8)
    t_stall_end = stalled[0] - win.t0 + STALL_S
    sch = system.schedule
    reads = np.nonzero(sch.kind == 0)[0]
    due = {int(sch.index[i]): float(sch.t[i]) for i in reads}
    during = [r for r, t in due.items() if t < t_stall_end - 0.05]
    assert len(during) >= 3
    for r in during:
        lat_s = win.served[r].e2e_ms / 1e3
        # the read waited out the rest of the stall after it fell due
        assert lat_s >= t_stall_end - due[r] - 1e-3, (r, lat_s)
