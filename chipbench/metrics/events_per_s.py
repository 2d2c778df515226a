"""Events the engine applied in the window over the window's length."""


def read(ctx):
    return ctx.counters["events_processed"] / ctx.window_s
