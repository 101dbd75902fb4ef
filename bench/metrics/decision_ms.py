"""decision_ms: the window over the replanning decisions made in it:
the mean time the served loop spends per event's decision, window
execution between decisions included."""


def read(ctx):
    n = ctx.work.get("decisions", 0)
    return 1e3 * ctx.window_s / n if n else None
