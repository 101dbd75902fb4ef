"""cold_replan_share.replay: replans that escalated past the fresh
solve (exchange search or ladder), as a share of all replans, from the
program's own ``StreamResult`` counters."""


def read(ctx):
    n = ctx.counters.get("replans", 0)
    return 100.0 * ctx.counters["cold_replans"] / n if n else None
