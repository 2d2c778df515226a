"""Set-up: process start to the window's start (generation, load through
the engine, warm-up and every compile or cache load), host clock."""


def read(ctx):
    return ctx.setup_s
