"""Megabytes one pod hands to the ring in one sync round, summed from the
shapes and dtypes of the arrays the round ships (an exact count)."""
import numpy as np


def read(ctx):
    arrays = ctx.program.round_payload()
    total = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                for a in arrays)
    return total / ctx.program.pods / 1e6
