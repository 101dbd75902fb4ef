"""setup_s: seconds from the process's start to the window's start
(imports, device start, compile-cache loads, inputs, warm-up)."""


def read(ctx):
    return ctx.setup_s
