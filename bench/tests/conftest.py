"""Shared fixtures of the harness's own tests: CPU, tiny sizes.

These tests sit outside the repository's tier-1 ``testpaths``; run them
with ``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``.
"""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


#: Files of the harness's own tests (a store with lexical lanes and its
#: keyword mix), looked for after bench/configs and bench/traffic.
DATA = os.path.join(ROOT, "bench", "tests", "data")


#: Metrics of the write mix (end-to-end, per-layer), which the tests drive
#: whether or not BENCHMARK.json lists a write cell.
WRITE_METRICS = ({"name": "write_p95_ms", "unit": "ms"},
                 {"name": "commit_ms", "unit": "ms"})


def _load(kind: str, name: str) -> dict:
    for d in (os.path.join(ROOT, "bench", kind), DATA):
        path = os.path.join(d, f"{name}.json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(f"no {kind} file {name}.json")


def tiny_cell(name: str, *, rate: float = 60.0):
    """``<config>.<traffic>`` from the files under bench/ (or the tests'
    own data) cut to a CPU test: a 4096-row x 128 arena, 3,000 docs,
    batches of at most 8, 32 sampled reads, every metric of
    BENCHMARK.json and the write mix's."""
    from bench import harness
    config, traffic = name.split(".", 1)
    c, mix = _load("configs", config), _load("traffic", traffic)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    c["store"].update(capacity=4096, dim=128)
    c["corpus"].update(n_docs=3000, chunk_rows=1000)
    c["check"]["sample_reads"] = 32
    c["serving"]["max_batch"] = 8
    mix["arrival"] = {"process": "poisson", "rate_per_s": rate}
    e2e, layer = list(bm["end_to_end"]), list(bm["per_layer"])
    for metrics, m in zip((e2e, layer), WRITE_METRICS):
        if m["name"] not in {x["name"] for x in metrics}:
            metrics.append(m)
    return harness.Cell(name=name, chips=1, config=c, mix=mix,
                        end_to_end=e2e, per_layer=layer)


@pytest.fixture
def tiny():
    return tiny_cell
