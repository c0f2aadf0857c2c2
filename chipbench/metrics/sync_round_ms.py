"""Device time of one execution of the sync-step program, in ms (mean over
the traced rounds and the cell's chips)."""
from chipbench import trace

PROGRAM = "jit__sync_step_impl"


def read(ctx):
    runs = trace.module_runs(ctx.trace, PROGRAM)
    durs = [e.dur for evs in runs.values() for e in evs]
    return sum(durs) / len(durs) / 1e6 if durs else None
