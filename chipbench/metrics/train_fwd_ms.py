"""Device time of the train step's forward pass, per step and chip, in ms:
its top-level operations under the ``train_forward`` scope and not under
a ``transpose(`` (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(scopes.of(ctx), "train_forward", ctx.traced.steps)
