"""Device busy time in the window over the ADMM iterations completed in
it (fits x iterations), in ms. Includes each fit's serving
factorization, spread over its iterations."""


def read(ctx):
    iters = ctx.counts.get("iters", 0)
    if not iters or ctx.trace.busy_s <= 0:
        return None
    return ctx.trace.busy_s / iters * 1e3
