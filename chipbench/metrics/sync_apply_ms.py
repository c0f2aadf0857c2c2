"""Device time of the sync round's apply (the peer's decode, unpacking,
the receiver's update and message norms; the average under model
averaging), per round and chip, in ms: its top-level operations under the
``sync_apply`` scope."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(scopes.of(ctx), "sync_apply", ctx.traced.rounds)
