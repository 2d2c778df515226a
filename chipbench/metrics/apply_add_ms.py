"""Device ms per engine step of the add path's programs: the tile-bound
probe and the add applier, matched by program name in the trace."""


def read(ctx):
    if ctx.trace is None or not ctx.counters["batches"]:
        return None
    secs, runs = ctx.trace.program_seconds(
        r"_add_tile_bound|apply_add_batch")
    return secs / ctx.counters["batches"] * 1e3 if runs else None
