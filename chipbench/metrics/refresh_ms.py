"""Device ms per read of the serving corpus's row refresh (trace)."""


def read(ctx):
    users = ctx.counters.get("read_users", 0)
    if ctx.trace is None or not users:
        return None
    secs, runs = ctx.trace.program_seconds(r"_refresh_corpus_rows")
    return secs / users * 1e3 if runs else None
