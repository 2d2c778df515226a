"""Engine steps per forget in the window (engine counters)."""
import numpy as np


def read(ctx):
    return float(np.mean(ctx.forget_steps)) if ctx.forget_steps else None
