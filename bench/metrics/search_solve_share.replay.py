"""search_solve_share.replay: the exchange search's solves as a share of
every cascade solve in the scans (program counters
``repro.scan.search_solves`` and ``repro.scan.replans``, one fresh solve
per replan)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.totals()["counters"]
    fresh = c.get("repro.scan.replans", 0)
    search = c.get("repro.scan.search_solves", 0)
    return 100.0 * search / (fresh + search) if fresh else None
