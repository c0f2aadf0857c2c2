"""Device time of one execution of the train-step program, in ms (mean
over the traced steps and the cell's chips)."""
from chipbench import trace

PROGRAM = "jit__train_step_impl"


def read(ctx):
    runs = trace.module_runs(ctx.trace, PROGRAM)
    durs = [e.dur for evs in runs.values() for e in evs]
    return sum(durs) / len(durs) / 1e6 if durs else None
