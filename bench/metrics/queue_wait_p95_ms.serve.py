"""95th percentile of the time requests waited in the scheduler's queue
(the `queue` stage of the program's request spans, summed over the slots
a request rode), in ms."""
from bench import timeline


def read(ctx):
    waits = [e["stages_ms"]["queue"] for e in ctx.spans
             if "queue" in e.get("stages_ms", {})]
    return timeline.percentile(waits, 95.0) if waits else None
