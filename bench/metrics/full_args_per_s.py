"""Args of the full-mode blocks the verifier accepted, over the window:
each arg is one jash evaluation and two SHA-256s (its submission hash
and its Merkle leaf)."""


def read(ctx):
    return sum(r.units["args"] for r in ctx.records
               if r.accepted) / ctx.window_s
