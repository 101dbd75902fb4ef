"""plan_dispatch_ms.served: host time per decision spent uploading the
live state and dispatching the cascade call (program span
``repro.plan.dispatch``), over the decisions (``repro.plan``)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    spans = obs.totals()["spans"]
    n = spans.get("repro.plan", {}).get("count", 0)
    part = spans.get("repro.plan.dispatch")
    return 1e3 * part["total_s"] / n if n and part else None
