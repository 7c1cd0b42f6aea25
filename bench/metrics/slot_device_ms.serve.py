"""Device busy time in the window over the slots dispatched in it, in ms:
the device cost of one served slot (local moments, DAC, aggregation)."""


def read(ctx):
    slots = ctx.counts.get("batches", 0)
    if not slots or ctx.trace.busy_s <= 0:
        return None
    return ctx.trace.busy_s / slots * 1e3
