"""Executor (`api/executor`): share of the hybrid scan's lexical loop that
compares a real query term, in percent: the ``qterms`` (real match() ids,
summed over a launch's rows) over ``rows`` x ``qt`` (the launch's
query-term bucket), summed over the window's hybrid ``launch`` spans."""
from bench.metrics._program_trace import launch_spans


def read(run):
    spans = [s for s in launch_spans(run)
             if s.ann.get("family") == "hybrid" and "qterms" in s.ann]
    slots = sum(int(s.ann["rows"]) * int(s.ann["qt"]) for s in spans)
    if not slots:
        return None
    return 100.0 * sum(int(s.ann["qterms"]) for s in spans) / slots
