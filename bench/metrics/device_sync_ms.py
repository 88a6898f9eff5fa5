"""Executor (`api/executor`): mean host time per dispatch unit spent in the
`device_sync` span (the first device_get of a launched scan: the wait for
the device plus the copy of its results)."""
import numpy as np


def read(run):
    seen = {(s.t0, s.t1) for tr in run.spans for s in tr.spans
            if s.name == "device_sync" and s.t1 is not None}
    if not seen:
        return None
    return 1e3 * float(np.mean([b - a for a, b in seen]))
