"""95th percentile over answered requests of the queue time in which no
slot of the request's tenant was in flight (`queue_split_ms.idle` of the
program's request spans: the batching timer and the worker's lag), in
ms. None where the request spans carry no split."""
from bench import timeline


def read(ctx):
    idle = [e["queue_split_ms"]["idle"] for e in ctx.spans
            if e.get("outcome") == "ok" and "queue_split_ms" in e]
    return timeline.percentile(idle, 95.0) if idle else None
