"""Operations and bytes of one arena scan, from the cell's shapes.

One scan answers a batch of query rows against every arena row. What it
has to move is one pass over the arena lanes as stored: the embedding
rows, the four metadata lanes (tenant, updated_at, category, acl; int32),
and, for the hybrid family, the T term-id lanes (int32) and T BM25 weight
lanes (float32). That count is the same whatever implements the scan, so a
scan that streams the arena twice reads as half its roofline. What it has
to compute is 2 * B * N * D multiply-adds for the similarity, counted at
the largest batch the scheduler drains (`max_batch`), against the chip's
bf16 peak: at every B <= 16 and D = 768 that time is a few percent of the
time the bytes take, so the scans are HBM-bound.
"""
from __future__ import annotations

import re

import numpy as np

#: Device programs that hold an arena scan: the jitted scan wrappers of the
#: filtered, grouped and hybrid families all compile as ``jit__run``; the
#: scan is the Pallas custom call inside them.
SCAN_MODULE = re.compile(r"^jit__run$")

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def pass_bytes(run, hybrid: bool) -> int:
    st = run.config["store"]
    n, d = int(st["capacity"]), int(st["dim"])
    b = n * d * DTYPE_BYTES[st.get("dtype", "float32")] + n * 4 * 4
    if hybrid:
        t = int(run.config["lexical"]["doc_terms"])
        b += n * t * (4 + 4)
    return b


def pass_flops(run) -> int:
    st = run.config["store"]
    return 2 * int(run.max_batch) * int(st["capacity"]) * int(st["dim"])


def least_seconds(run, hybrid: bool) -> tuple[float, str]:
    """The least time one scan can take on this chip, and its bound."""
    t_bytes = pass_bytes(run, hybrid) / run.peaks["hbm_bytes_per_s"]
    t_flops = pass_flops(run) / run.peaks["bf16_flops_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "mxu")


def scan_ops(run) -> list:
    if run.profile is None:
        return []
    return [o for o in run.profile.in_window()
            if o.kernel and SCAN_MODULE.match(o.module)]


def roofline_pct(run, engine: str) -> float | None:
    """Share of the roofline over every scan of the window, in percent:
    scans times their least time, over their summed device time. None
    where this family did not serve every read of the run."""
    if set(run.engines) != {engine}:
        return None
    ops = scan_ops(run)
    if not ops:
        return None
    least, _ = least_seconds(run, hybrid=engine == "hybrid")
    spent = float(np.sum([o.dur for o in ops]))
    return 100.0 * len(ops) * least / spent
