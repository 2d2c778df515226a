"""Device ms per read of the serving programs (trace, by program name)."""
from roofline import SERVING


def read(ctx):
    users = ctx.counters.get("read_users", 0)
    if ctx.trace is None or not users:
        return None
    secs, runs = ctx.trace.program_seconds(SERVING)
    return secs / users * 1e3 if runs else None
