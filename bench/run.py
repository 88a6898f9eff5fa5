"""Run one cell of BENCHMARK.json and print its result line.

    python3 bench/run.py --workload dense768.levels --seed 7 --seconds 20 \
        --trace 0

Progress goes to stdout; the last line of stdout is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
with ``--trace 1``, ``breakdown``), and the numbers the check compared are
the last lines of stderr. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled window. With
no TPU, or fewer chips than the cell asks for, it exits 1 and prints no
result. The JAX compilation cache is the program's own:
``$JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    from bench import harness
    harness.configure_cache()

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                       t_proc0=T_PROC0)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
