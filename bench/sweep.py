#!/usr/bin/env python3
"""Knee sweep of a serving cell: one set-up, then one window per offered
rate, each printed as a JSON line (p95 from due times, misses, how late
the generator ran). The cell's fixed rate is set from this sweep once, by
hand; the benchmark's runs never search for a rate.

    python3 bench/sweep.py --workload <serving cell> --seed <n> \\
        --seconds <s> --rates 40,60,80
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell, cfg, traffic, *_ = harness.cell_spec(args.workload)
    _, device = harness.device_info()
    if device["platform"] != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    harness.configure_jax()
    drv = harness.driver_class(traffic["driver"])(cfg, traffic, args.seed,
                                                  args.seconds)
    drv.setup()
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        drv.make_timeline(rate)
        e2e = drv.window(args.seconds)
        c = drv.counts
        print(json.dumps(dict(e2e, rate=rate, attempted=c["attempted"],
                              missed=c["failed"],
                              rows=c["rows"], drain_s=c["window_s"]
                              - args.seconds, **drv.notes)), flush=True)
    drv.sched.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
