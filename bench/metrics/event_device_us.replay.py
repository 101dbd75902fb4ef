"""event_device_us.replay: device busy time per control-plane event."""


def read(ctx):
    if ctx.trace is None or not ctx.work["events"]:
        return None
    return 1e6 * ctx.trace.busy_s / ctx.work["events"]
