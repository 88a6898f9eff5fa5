"""Per-layer metric readers, one file each, found by metric name.

``bench/metrics/<name>.py`` defines ``read(run) -> float | None``: it takes
one number from the traced run (`bench.harness.RunRecord`: host timings,
program spans and counters, the profiler trace) and returns None where the
run holds nothing for it to read, so the metric is left out of the line.
A reader never stands in a 0 for what it could not read.
"""
from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def reader(name: str):
    """The ``read`` function of metric ``name``."""
    path = os.path.join(HERE, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
