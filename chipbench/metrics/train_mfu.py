"""Model FLOPs of one train step over the device time of the train-step
program, as a share of a chip's bf16 peak, in % (the sync rounds are left
out: they are ``sync_round_ms``'s)."""
from chipbench import costs, trace

PROGRAM = "jit__train_step_impl"


def read(ctx):
    runs = trace.module_runs(ctx.trace, PROGRAM)
    durs = [e.dur for evs in runs.values() for e in evs]
    if not durs:
        return None
    step_s = sum(durs) / len(durs) / 1e9
    p = ctx.program
    tokens = p.pods // ctx.chips * p.rows * p.seq
    flops = costs.train_flops_per_token(ctx.config, p.seq) * tokens
    return 100.0 * flops / step_s / ctx.peaks["bf16_flops"]
