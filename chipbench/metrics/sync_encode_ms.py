"""Device time of the sync round's encode (the accumulated gradient's
average, packing, the error-feedback fold, the codec's encode), per round
and chip, in ms: its top-level operations under the ``sync_encode``
scope."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(scopes.of(ctx), "sync_encode", ctx.traced.rounds)
