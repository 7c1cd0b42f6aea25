"""Whole-step share of the chip's peak in the window, in %: the needed
flops of the training runs completed in it (bench/work.py: every ADMM
iteration and the serving factorization) over the device's busy time
(the union of its operation intervals in the trace), read against the
bf16 peak: the work is compute-bound, and no float32 peak is published."""
from bench import peaks, work


def read(ctx):
    c = ctx.counts
    if not c.get("fits") or ctx.trace.busy_s <= 0:
        return None
    flops = c["fits"] * work.fit(c["agents"], c["n"], c["dim"],
                                 c["iters"] // c["fits"])
    return peaks.flops_share(flops, ctx.trace.busy_s, ctx.device_kind)
