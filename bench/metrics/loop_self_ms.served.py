"""loop_self_ms.served: the served loop's own host time per decision:
event sorting, queue and slot bookkeeping, metrics (self time of the
program span ``repro.loop``), over the decisions (``repro.plan``)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    spans = obs.totals()["spans"]
    n = spans.get("repro.plan", {}).get("count", 0)
    part = spans.get("repro.loop")
    return 1e3 * part["self_s"] / n if n and part else None
