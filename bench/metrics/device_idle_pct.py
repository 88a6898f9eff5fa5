"""Device: share of the traced window in which no operation ran on the
chip, in percent (1 - union of device operation intervals / window)."""


def read(run):
    p = run.profile
    if p is None or p.window_s() <= 0 or not p.ops:
        return None
    return 100.0 * (1.0 - p.busy_s() / p.window_s())
