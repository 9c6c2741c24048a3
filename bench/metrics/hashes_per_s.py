"""Nonces double-SHA-256'd in the blocks the verifier accepted, over the
window (Bitcoin's H/s)."""


def read(ctx):
    return sum(r.units["hashes"] for r in ctx.records
               if r.accepted) / ctx.window_s
