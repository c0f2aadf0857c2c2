"""Device time of the train step's update (clipping, the gradient
accumulation of ASGD-GA, the optimizer), per step and chip, in ms: its
top-level operations under the ``train_update`` scope."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(scopes.of(ctx), "train_update", ctx.traced.steps)
