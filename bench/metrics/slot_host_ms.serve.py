"""Host time per dispatched slot, in ms: the mean over the program's slot
events of pack + dispatch + fetch + deliver (the slot's host wall time
without the wait for the device). None where the program emits no slot
events."""


def read(ctx):
    host = [sum(e["slot_ms"][k] for k in ("pack", "dispatch", "fetch",
                                          "deliver"))
            for e in ctx.spans if e.get("event") == "slot"]
    return sum(host) / len(host) if host else None
