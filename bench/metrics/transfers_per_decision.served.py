"""transfers_per_decision.served: arrays moved between host and device
per decision, both ways (program counters ``repro.h2d`` and
``repro.d2h``), over the decisions (``repro.plan``)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    t = obs.totals()
    n = t["spans"].get("repro.plan", {}).get("count", 0)
    c = t["counters"]
    moved = c.get("repro.h2d", 0) + c.get("repro.d2h", 0)
    return moved / n if n and moved else None
