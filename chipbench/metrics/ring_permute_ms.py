"""Device time of the sync round's collective-permutes, per round and
chip, in ms."""
from chipbench import trace

PROGRAM = "jit__sync_step_impl"


def read(ctx):
    per_dev = trace.op_time_ns(ctx.trace, r"collective-permute", PROGRAM)
    if not per_dev:
        return None
    mean = sum(per_dev.values()) / len(ctx.trace.devices)
    return mean / ctx.traced.rounds / 1e6
