"""Device time of the sync round's ring send (the roll over pods, a
collective-permute across chips), per round and chip, in ms: its
top-level operations under the ``sync_ring`` scope."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(scopes.of(ctx), "sync_ring", ctx.traced.rounds)
