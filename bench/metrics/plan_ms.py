"""Front door and planner (`api/ragdb`, `api/planner`): median host time
to lower one request through its session and compile its plan, timed by
the benchmark around the builder chain and `QueryBuilder.plan()`."""
import numpy as np


def read(run):
    return float(np.median(run.plan_ms)) if run.plan_ms else None
