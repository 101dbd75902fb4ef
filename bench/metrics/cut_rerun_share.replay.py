"""cut_rerun_share.replay: windows the scans ran a second time to stop at
a first completion, as a share of the windows (program counters
``repro.scan.cut_reruns`` and ``repro.scan.n_windows``)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.totals()["counters"]
    n = c.get("repro.scan.n_windows", 0)
    return 100.0 * c.get("repro.scan.cut_reruns", 0) / n if n else None
