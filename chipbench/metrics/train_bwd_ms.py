"""Device time of the train step's backward pass, remat recomputation in
it, per step and chip, in ms: its top-level operations under the
``train_forward`` scope and a ``transpose(`` (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(scopes.of(ctx), scopes.BACKWARD, ctx.traced.steps)
