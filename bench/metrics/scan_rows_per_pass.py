"""Executor (`api/executor`): real query rows per pass of the arena-scan
kernel over the window's launches, sum of ``rows`` over sum of ``passes``
of the ``launch`` spans (8 fills every pass; bucket padding and partly
filled row blocks lower it)."""
from bench.metrics._program_trace import launch_spans


def read(run):
    spans = [s for s in launch_spans(run) if "passes" in s.ann]
    passes = sum(int(s.ann["passes"]) for s in spans)
    if not passes:
        return None
    return sum(int(s.ann["rows"]) for s in spans) / passes
