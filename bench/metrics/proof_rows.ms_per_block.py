"""Median over the window's blocks of the time the miner spends on the
block's proof rows and their Merkle root (the ``model_train.proof`` span
under ``node.mine_block``), in ms."""
from bench import program_spans


def read(ctx):
    w = program_spans.window(ctx)
    return None if w is None else w.median_ms("node.mine_block",
                                              "model_train.proof")
