"""Find a cell's knee: one set-up, then one open-loop window per offered
rate, low to high.

    python3 bench/sweep.py --workload dense768.levels --seed 5 \
        --seconds 6 --rates 100,200,300,400

Prints one JSON line per rate: offered and completed rate, read latency
percentiles from due time, the queue wait's 95th percentile, how long the
last read finished after the window closed, and the write p95 where the
mix writes. The knee is the highest rate the system sustains without a
backlog that grows through the window; a cell's mix runs at about 0.8 of
it. Needs the chip (the same check as `bench/run.py`).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    import numpy as np
    from bench import harness
    harness.configure_cache()
    from bench.traffic import make_schedule

    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("the sweep needs a TPU", file=sys.stderr)
        return 1
    rates = [float(r) for r in args.rates.split(",")]
    system = harness.build(cell, args.seed, args.seconds, rate=rates[0])
    harness.warm(system)
    max_batch = int(cell.config["serving"]["max_batch"])
    for i, rate in enumerate(rates):
        system.schedule = make_schedule(cell.mix, system.corpus,
                                        args.seed + i, args.seconds,
                                        meta=system.meta, rate=rate)
        system.writes_done = 1 if system.schedule.n_writes else 0
        win = harness.drive(system, args.seconds, max_batch=max_batch)
        done = [r for r in win.served.values() if r.served != "failed"]
        lat = np.asarray([r.e2e_ms for r in done])
        wait = np.asarray([r.queue_wait_ms for r in done])
        out = {"rate": rate, "reads": system.schedule.n_reads,
               "finished": len(done),
               "qps": len(done) / (win.t_end - win.t0),
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "p99_ms": float(np.percentile(lat, 99)),
               "queue_wait_p95_ms": float(np.percentile(wait, 95)),
               "drain_s": win.t_end - win.t0 - args.seconds,
               "compiles": win.compiles}
        if win.write_ms:
            out["write_p95_ms"] = float(np.percentile(win.write_ms, 95))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
