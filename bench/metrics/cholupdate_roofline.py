"""Share of its roofline that the rank-1 Cholesky update kernel
(`kernels/cholupdate.py`) reached in the window, in %: one update of
each agent's (W-1)^2 trailing factor per event (bench/work.py), over the
device time of the events that carry the kernel's name."""
from bench import peaks, work


def read(ctx):
    seconds, events = ctx.trace.kernel_seconds("cholupdate")
    if not events:
        return None
    c = ctx.counts
    flops, nbytes = work.cholupdate(c["window"] - 1)
    calls = c["events"] * c["agents"]
    share, _ = peaks.roofline_share(calls * flops, calls * nbytes, seconds,
                                    ctx.device_kind)
    return share
