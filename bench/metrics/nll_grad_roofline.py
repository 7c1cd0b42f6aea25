"""Share of its roofline that the fused NLL-gradient kernel
(`kernels/nll_grad.py`) reached in the window, in %: the needed work of
every agent's gradient in every iteration (bench/work.py) at the chip's
peaks, over the device time of the events that carry the kernel's name."""
from bench import peaks, work


def read(ctx):
    seconds, events = ctx.trace.kernel_seconds("nll_grad")
    if not events:
        return None
    c = ctx.counts
    flops, nbytes = work.nll_grad(c["n"], c["dim"])
    calls = c["iters"] * c["agents"]
    share, _ = peaks.roofline_share(calls * flops, calls * nbytes, seconds,
                                    ctx.device_kind)
    return share
