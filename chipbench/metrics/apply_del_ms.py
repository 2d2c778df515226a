"""Device ms per run of the basket-deletion applier (trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    secs, runs = ctx.trace.program_seconds(r"apply_del_basket_batch")
    return secs / runs * 1e3 if runs else None
