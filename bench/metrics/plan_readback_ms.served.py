"""plan_readback_ms.served: host time per decision spent reading the
cascade's answer back (program span ``repro.plan.readback``), over the
decisions (``repro.plan``)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    spans = obs.totals()["spans"]
    n = spans.get("repro.plan", {}).get("count", 0)
    part = spans.get("repro.plan.readback")
    return 1e3 * part["total_s"] / n if n and part else None
