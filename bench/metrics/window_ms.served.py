"""window_ms.served: time per decision spent executing windows between
decisions, re-runs at a first completion included (program span
``repro.window``), over the decisions (``repro.plan``)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    spans = obs.totals()["spans"]
    n = spans.get("repro.plan", {}).get("count", 0)
    part = spans.get("repro.window")
    return 1e3 * part["total_s"] / n if n and part else None
