"""Device time of the sync round's error feedback (the sender's decode of
its own message, the new residual and its norm), per round and chip, in
ms: its top-level operations under the ``sync_ef`` scope."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(scopes.of(ctx), "sync_ef", ctx.traced.rounds)
