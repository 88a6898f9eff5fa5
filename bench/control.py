"""Readings that the limits of a cell's check are set from.

    python3 bench/control.py --workload dense768.levels --seconds 6 \
        --seeds 11,12,13,14,15,16,17,18,19,20,21,22 --control-seeds 11,12,13

For every seed, in one process: the cell's set-up, a window at the cell's
own load, and the comparison of a seeded sample of served reads with the
reference, as `bench/run.py` makes them (the program's readings). For the
control seeds also the control's readings: the reference scan itself, with
its similarity computed in the three-pass bf16 product
(`Precision.HIGH`, the step below the configuration's exact f32) and, with
lexical lanes, its BM25 lane weights rounded to bf16, put in the system's
place and judged by the same comparison. Where the mix writes, the control
also stores each written row rounded to bf16 and reads it back. One JSON
line per seed. Needs the chip; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, seconds: float, control: bool, *,
             say=print) -> dict:
    """The program's (and optionally the control's) compared numbers for
    one seed. Works on any backend (the harness's tests run it on CPU)."""
    import dataclasses

    import ml_dtypes
    import numpy as np

    from bench import harness

    system = harness.build(cell, seed, seconds, say=say)
    harness.warm(system, say=say)
    max_batch = int(cell.config["serving"]["max_batch"])
    win = harness.drive(system, seconds, max_batch=max_batch)
    out: dict = {"seed": seed}
    if system.schedule.n_writes:
        emb, ts = harness.readback(system)
        ref_w = harness.make_reference(system)
        out["program_readback_gap"] = ref_w.readback_gap(emb, ts)
        if control:
            w = ref_w.w_emb.astype(np.float64)
            last = [np.nonzero(ref_w.w_doc == d)[0][-1] for d in ref_w.touched]
            unit = w[last] / np.linalg.norm(w[last], axis=1, keepdims=True)
            stored = unit.astype(ml_dtypes.bfloat16).astype(np.float32)
            out["control_readback_gap"] = ref_w.readback_gap(
                stored, ref_w.w_ts[last])
    reads = harness.sample_reads(system, win,
                                 int(cell.config["check"]["sample_reads"]))
    system.db = None
    del win
    gc.unfreeze()
    gc.collect()
    ref = harness.make_reference(system)
    limits = cell.config["check"]["limits"]
    served = (np.stack([r.slots for r in reads]),
              np.stack([r.scores for r in reads]))
    checks, detail = harness.compare(ref, reads, served, limits)
    out["program"] = {k: v for k, (v, _) in checks.items()}
    out["program_detail"] = detail
    if control:
        # the control answers on the corpus as generated: judge it so
        base = [dataclasses.replace(r, writes_before=0) for r in reads]
        ctl = ref.answer_lists(base, precision="high",
                               lex_bf16=ref.lex is not None)
        lists = ref.scan(base)
        j = ref.judge(base, lists, ctl, tol=limits["score_gap"])
        out["control"] = {k: j[k] for k in ("score_gap", "rrf_mismatch",
                                             "ref_undecided")}
        out["control_detail"] = j
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    from bench import harness
    harness.configure_cache()

    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("the control readings need a TPU", file=sys.stderr)
        return 1
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for s in (int(s) for s in args.seeds.split(",")):
        r = readings(cell, s, args.seconds, s in ctl)
        print(json.dumps(r, default=float), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
