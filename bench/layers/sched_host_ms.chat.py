"""Host time the serving engine spends per tick in admission and page
reclaim: the mean per tick of the program's ``admission`` plus
``reclaim`` span durations (its telemetry tracer) inside the traced
window, in milliseconds."""


def read(ctx):
    spans = [e for e in ctx["spans"] if e.get("ph") == "X"]
    ticks = [e for e in spans if e["name"] == "admission"]
    if not ticks:
        return None
    host = sum(e["dur"] for e in spans
               if e["name"] in ("admission", "reclaim"))
    return 1e3 * host / len(ticks)
