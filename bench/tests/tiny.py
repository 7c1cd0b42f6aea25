"""Small CPU-sized versions of the benchmark's cells, run through the
harness's own `run_cell` (everything but the look for a chip)."""
from __future__ import annotations

import time

import jax

from bench import harness

TRAFFIC = {
    "serve": {"slot": 64, "rows": [1, 127], "rate_per_s": 40.0,
              "check_rows": 128, "queue_depth": 4096},
    "train": {"admm_iters": 20},
    "stream": {"pool": 64},
}
SIZE = {"serve": (512, 4), "train": (512, 8), "stream": (512, 4)}


def run(workload: str, seed: int = 2**33 + 7, seconds: float = 0.5,
        fault=None, control: bool = False) -> dict:
    """One run of a small copy of `workload`, under `fault` (a context
    manager of bench.faults) when given, or with the control in the
    program's place, against the cell's own limits."""
    cell, cfg, traffic, limits, e2e, layer, units = \
        harness.cell_spec(workload)
    kind = traffic["driver"]
    n, m = SIZE[kind]
    cfg = dict(cfg, n_train=n, num_agents=m, rff_features=128)
    traffic = dict(traffic, **TRAFFIC[kind])
    jax.clear_caches()
    try:
        if fault is None:
            return harness.run_cell(workload, cfg, traffic, limits, seed,
                                    seconds, False, time.perf_counter(),
                                    e2e, layer, units, control)
        with fault():
            return harness.run_cell(workload, cfg, traffic, limits, seed,
                                    seconds, False, time.perf_counter(),
                                    e2e, layer, units)
    finally:
        jax.clear_caches()
