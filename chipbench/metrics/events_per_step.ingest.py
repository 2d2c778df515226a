"""Events applied per engine step in the window (engine counters)."""


def read(ctx):
    steps = ctx.counters["batches"]
    return ctx.counters["events_processed"] / steps if steps else None
