"""Device: mean wait of a launched scan in the chip's queue, from the end
of its ``rag.launch`` event on the host to the start of its scan operation
on the chip (launches and operations paired in dispatch order,
`_program_trace.paired`)."""
import numpy as np

from bench.metrics._program_trace import paired


def read(run):
    pairs = paired(run)
    if pairs is None:
        return None
    return 1e3 * float(np.mean([op.t0 - ev.t1 for ev, op in pairs]))
