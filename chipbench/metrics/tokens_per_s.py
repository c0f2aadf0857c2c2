"""Tokens all pods trained in the window over the window's wall time (the
window ends in ``block_until_ready`` on the state; sync rounds are in it)."""


def read(ctx):
    return ctx.window.tokens / ctx.window.seconds
