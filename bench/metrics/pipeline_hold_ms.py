"""Scheduler (`serving/scheduler`): mean host time a launched batch waits
in the scheduler's pipeline, from joining it to the start of its finish in
a later round (the ``pending`` span, one per batch)."""
import numpy as np


def read(run):
    seen = {(s.t0, s.t1) for tr in run.spans for s in tr.spans
            if s.name == "pending" and s.t1 is not None}
    if not seen:
        return None
    return 1e3 * float(np.mean([b - a for a, b in seen]))
