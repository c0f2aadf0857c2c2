"""Seconds from process start to the first timed step: imports, device
set-up, weights, compilation or cache loads, the checked steps and the
warm-up cycle."""


def read(ctx):
    return ctx.setup_s
