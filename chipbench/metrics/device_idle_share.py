"""Share of the traced window in which no operation ran on a chip, in %,
averaged over the cell's chips."""
from chipbench import trace


def read(ctx):
    tr, w = ctx.trace, ctx.traced
    if not tr.devices:
        return None
    span = w.hi - w.lo
    idle = [1.0 - trace.busy_ns(tr, d, w.lo, w.hi) / span
            for d in tr.devices]
    return 100.0 * sum(idle) / len(idle)
