"""Median over the window's blocks of the time the miner spends making
the block's microbatches and stacking them (the ``model_train.batches``
span under ``node.mine_block``), in ms."""
from bench import program_spans


def read(ctx):
    w = program_spans.window(ctx)
    return None if w is None else w.median_ms("node.mine_block",
                                              "model_train.batches")
