"""Whole-step share of the roofline in the window, in %: the needed work
of the observation events completed in it (bench/work.py: flops, and
each factor's triangle read and written once) over the device's busy
time (the union of its operation intervals in the trace), read against
the bound that applies at these shapes: HBM bandwidth."""
from bench import peaks, work


def read(ctx):
    c = ctx.counts
    if not c.get("events") or ctx.trace.busy_s <= 0:
        return None
    flops, nbytes = work.observe_event(c["agents"], c["window"], c["dim"])
    share, _ = peaks.roofline_share(c["events"] * flops,
                                    c["events"] * nbytes,
                                    ctx.trace.busy_s, ctx.device_kind)
    return share
