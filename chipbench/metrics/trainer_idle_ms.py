"""Device idle time inside the trainer's own ``repro.*`` spans (its step
and sync dispatches), per step and chip, in ms: the host work of the
trainer that the device waits on (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(ctx):
    tr, w = ctx.trace, ctx.traced
    sc = scopes.of(ctx)
    if not sc.program_spans or not tr.devices:
        return None
    idle = [scopes.idle_in_program_spans_ns(tr, sc, d, w.lo, w.hi)
            for d in tr.devices]
    return sum(idle) / len(idle) / w.steps / 1e6
