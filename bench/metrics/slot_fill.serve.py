"""Share of the dispatched slots' rows that carried requests, in %:
the sum of the slot events' real rows over the sum of their slot sizes.
None where the program emits no slot events."""


def read(ctx):
    slots = [e for e in ctx.spans if e.get("event") == "slot"]
    size = sum(e["slot"] for e in slots)
    return 100.0 * sum(e["rows"] for e in slots) / size if size else None
