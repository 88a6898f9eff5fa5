"""Share of its HBM roofline that the dense arena scan (filtered and
grouped families, `kernels/arena_scan`) reaches over the window, from the
device trace; operations and bytes in `_scan_cost`."""
from bench.metrics._scan_cost import roofline_pct


def read(run):
    return roofline_pct(run, "pallas")
