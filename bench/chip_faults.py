#!/usr/bin/env python3
"""Read each planted fault of a cell on the chip, at the cell's own size.

    python3 bench/chip_faults.py --workload <cell> --seeds 1,2,3 \\
        --seconds 2 [--faults name,...]

For every seed and fault (bench/faults.py; the name `control` puts the
control of bench/control.py in the program's place) one run of the cell
is made in this process, and one JSON line printed with the numbers
compared; the limits of bench/limits/<cell>.json are set between the
sound runs' readings and these. The benchmark's own runs never plant a
fault nor run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", default=None)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import faults, harness
    cell, cfg, traffic, limits, e2e, layer, units = \
        harness.cell_spec(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("chip_faults: no TPU", file=sys.stderr)
        return 1
    harness.configure_jax()
    kinds = faults.FAULTS[traffic["driver"]]
    names = (args.faults.split(",") if args.faults
             else ["control"] + sorted(kinds))
    for seed in (int(s) for s in args.seeds.split(",")):
        for name in names:
            jax.clear_caches()
            ctl = name == "control"
            with (contextlib.nullcontext() if ctl else kinds[name]()):
                r = harness.run_cell(args.workload, cfg, traffic, limits,
                                     seed, args.seconds, False,
                                     time.perf_counter(), e2e, layer, units,
                                     control=ctl)
            print(json.dumps({"fault": name, "seed": seed,
                              "correct": r["correct"],
                              "checks": r["checks"],
                              "notes": r["notes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
