#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`bench/configs/<config>.json`) under a traffic mix
(`bench/traffic/<traffic>.json`, whose `driver` names the module of
`bench/drivers/` that drives it). Set-up makes the data from the seed and
warms every shape the mix uses; the window then runs for `--seconds`; the
outputs of the window are compared with the plain reference
(`bench/reference.py`) against the cell's limits (`bench/limits/<cell>.json`).
With `--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, each read by `bench/metrics/<metric>.py`
from the profiler trace of the window and from the run's counters.

Exits non-zero with no result line when JAX finds no TPU, or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
