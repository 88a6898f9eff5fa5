"""Transactions (`core/transactions`): device time of the commit programs
(`jit_update`) per write committed in the window, from the device trace."""
import re

COMMIT_MODULE = re.compile(r"^jit_update$")


def read(run):
    if run.profile is None or not run.writes_in_window:
        return None
    ops = [o for o in run.profile.in_window()
           if COMMIT_MODULE.match(o.module)]
    if not ops:
        return None
    return 1e3 * sum(o.dur for o in ops) / run.writes_in_window
