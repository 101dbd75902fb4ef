"""scan_host_ms.<cell>: host time per event scan before the device runs
and after it ends: event arrays, uploads, initial state, dispatch, then
the read-back and metrics (program spans ``repro.scan.prepare`` and
``repro.scan.finish``), over the scans (``repro.scan``)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    spans = obs.totals()["spans"]
    n = spans.get("repro.scan", {}).get("count", 0)
    parts = [spans.get(f"repro.scan.{k}") for k in ("prepare", "finish")]
    if not n or None in parts:
        return None
    return 1e3 * sum(p["total_s"] for p in parts) / n
