"""90th percentile of the window's step times, a step being the interval
between successive loss reads (a round lands in the step after it)."""
import statistics


def read(ctx):
    steps = [s * 1e3 for s in ctx.window.step_s]
    return statistics.quantiles(steps, n=10, method="inclusive")[8]
