"""Scheduler (`serving/scheduler`): 95th percentile of the wait from a
read's due time to the launch of its batch (`ServedResult.queue_wait_ms`,
with the due time as `ServeRequest.arrival_t`)."""
import numpy as np


def read(run):
    w = run.queue_wait_ms
    return float(np.percentile(w, 95)) if len(w) else None
