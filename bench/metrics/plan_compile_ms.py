"""Front door and planner (`api/ragdb`): median host time of the planner's
compile of one read (`RagDB.compile`, the ``plan_compile`` span). The
session and builder chain that `plan_ms` also times lie outside it."""
import numpy as np


def read(run):
    d = [s.t1 - s.t0 for tr in run.spans for s in tr.spans
         if s.name == "plan_compile" and s.t1 is not None]
    return 1e3 * float(np.median(d)) if d else None
