"""Tokens trained in the window's committed blocks over the window."""


def read(ctx):
    return sum(r.units["tokens"] for r in ctx.records
               if r.accepted) / ctx.window_s
