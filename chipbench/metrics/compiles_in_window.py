"""Executables compiled or loaded inside the measured window (JAX's
backend-compile event); the warm-up should leave none."""


def read(ctx):
    return ctx.window.compiles
