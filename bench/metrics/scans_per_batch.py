"""Executor (`api/executor`): hybrid arena scans per launched batch, the
window's hybrid ``launch`` spans over the distinct ``batch`` numbers they
carry. A batch splits into one scan per fuse key (fusion mode and
query-term bucket), each a full stream of the arena."""
from bench.metrics._program_trace import launch_spans


def read(run):
    batches = [s.ann["batch"] for s in launch_spans(run)
               if s.ann.get("family") == "hybrid"
               and s.ann.get("batch") is not None]
    if not batches:
        return None
    return len(batches) / len(set(batches))
