"""stream_events_per_s: control-plane events (arrivals, budget steps,
end marks) of every tape served in the window, over the window."""


def read(ctx):
    return ctx.work["events"] / ctx.window_s
