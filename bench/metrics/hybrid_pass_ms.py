"""Arena-scan kernel (`kernels/arena_scan`), hybrid family: device time of
one pass of the hybrid scan, the device time of the scan operations paired
with hybrid ``rag.launch`` events over the ``passes`` those launches
asked for (one per 8-row query block)."""
from bench.metrics._program_trace import paired


def read(run):
    pairs = [(ev, op) for ev, op in paired(run) or ()
             if ev.stats.get("family") == "hybrid"]
    if not pairs or any("passes" not in ev.stats for ev, _ in pairs):
        return None
    passes = sum(int(ev.stats["passes"]) for ev, _ in pairs)
    return 1e3 * sum(op.dur for _, op in pairs) / passes
