"""The benchmark's run of one cell: set-up, window, comparison, result.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell is found by name in a file of its own:

  bench/configs/<config>.json     the fleet (sizes, hyperparameters,
                                  source, `assumed`, `reduced`)
  bench/traffic/<traffic>.json    the mix's parameters; its `driver` key
                                  names bench/drivers/<driver>.py
  bench/metrics/<metric>.py       `read(ctx)` -> value, or None when the
                                  run has nothing for it to read; a
                                  metric <family>.<kind> with no file of
                                  its own is read by <family>.py
  bench/limits/<cell>.json        the limit of each number compared

so a later change adds a configuration, a mix, a metric or a cell by
adding files and BENCHMARK.json entries, and edits nothing here.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

from bench import tracefile

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_class(name: str):
    return _module(BENCH / "drivers" / f"{name}.py").Driver


def metric_reader(name: str):
    """`read` of bench/metrics/<name>.py, or, where a metric has no file of
    its own, of its family's: the name up to its last dot
    (`device_idle.serve` -> device_idle.py)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return _module(path).read


def cell_spec(workload: str, bench_json: Path = ROOT / "BENCHMARK.json"):
    """(cell, config, traffic, limits, end_to_end names, per_layer names)
    of one workload of BENCHMARK.json."""
    spec = load_json(bench_json)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(ROOT / conf["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{workload}.json")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m["name"] for m in spec["end_to_end"] if applies(m)]
    layer = [m["name"] for m in spec["per_layer"] if applies(m)]
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    return cell, cfg, traffic, limits, e2e, layer, units


def device_info():
    import jax
    devs = jax.devices()
    return devs, {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}


def _memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def configure_jax():
    """The program's runtime settings (float32 matmuls, the compilation
    cache at a fixed path in the checkout), and every compiled program
    written to that cache, however fast it compiled."""
    import jax
    from repro.launch.runtime import configure_runtime
    cache = configure_runtime()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def run_cell(workload: str, cfg: dict, traffic: dict, limits: dict,
             seed: int, seconds: float, trace: bool, t_start: float,
             e2e_names, layer_names, units, control: bool = False) -> dict:
    """One run of one cell on the default device -> the result dict.
    With `control`, the control (bench/control.py) takes the program's
    place in the comparison; the benchmark's own runs never set it."""
    import jax
    devs, device = device_info()
    drv = driver_class(traffic["driver"])(cfg, traffic, seed, seconds)
    drv.setup()
    # what set-up made lives to the end: keep the collector off it, so
    # that no long collection pauses the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        # device operations and the host's TraceMe spans; no Python
        # function tracing (it would slow the host path being measured)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        e2e = drv.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
        gc.unfreeze()
    memory_peak = _memory_peak(devs)
    drv.collect()
    gc.collect()
    if control:
        drv.control()
    checks = drv.compare(limits)

    reduced = None
    if trace:
        try:
            reduced = tracefile.reduce(*tracefile.read_xplane(tdir))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    metrics = {}
    if trace:
        ctx = SimpleNamespace(trace=reduced, counts=drv.counts,
                              spans=drv.spans, device_kind=device["kind"],
                              cfg=cfg, traffic=traffic)
        for name in layer_names:
            value = metric_reader(name)(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for name in e2e_names:
            metrics[name] = {"value": values[name], "unit": units[name]}

    device["memory_peak_bytes"] = memory_peak
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    result = {"correct": correct, "attempted": drv.counts["attempted"],
              "failed": drv.counts["failed"], "metrics": metrics,
              "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["notes"] = drv.notes
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def main(args, t_start: float) -> int:
    cell, cfg, traffic, limits, e2e, layer, units = cell_spec(args.workload)
    _, device = device_info()
    chips = int(cell["chips"])
    if device["platform"] != "tpu" or device["count"] < chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX sees "
              f"{device['count']} {device['platform']} device(s)",
              file=sys.stderr)
        return 1
    configure_jax()
    result = run_cell(args.workload, cfg, traffic, limits, args.seed,
                      args.seconds, bool(args.trace), t_start, e2e, layer,
                      units)
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
