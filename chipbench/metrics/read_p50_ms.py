"""Median, over the reads due in the window, of due time to the return
of ``recommend``."""
import numpy as np


def read(ctx):
    return float(np.median(ctx.read_ms)) if ctx.read_ms else None
