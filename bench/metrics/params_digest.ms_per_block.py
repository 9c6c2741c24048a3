"""Median over the window's blocks of the time the miner spends in
``params_digest`` (the ``params_digest`` span under ``node.mine_block``:
the post-block params fetched to the host and hashed), in ms."""
from bench import program_spans


def read(ctx):
    w = program_spans.window(ctx)
    return None if w is None else w.median_ms("node.mine_block",
                                              "params_digest")
