"""Arena-scan kernel (`kernels/arena_scan`): device time of one pass over
the arena, the scans' device time (the operations the roofline reads,
`_scan_cost.scan_ops`) over the passes their launches asked for (one per
8-row query block, the ``passes`` of each ``rag.launch``)."""
from bench.metrics._program_trace import paired


def read(run):
    pairs = paired(run)
    if pairs is None or any("passes" not in ev.stats for ev, _ in pairs):
        return None
    passes = sum(int(ev.stats["passes"]) for ev, _ in pairs)
    return 1e3 * sum(op.dur for _, op in pairs) / passes
