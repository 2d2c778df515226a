"""Median, over the forgets due in the window, of due time to the return
of ``forget_user``."""
import numpy as np


def read(ctx):
    return float(np.median(ctx.forget_ms)) if ctx.forget_ms else None
