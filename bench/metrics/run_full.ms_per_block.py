"""Median over the window's blocks of the miner's summed
``executor.run_full.chunk`` spans (each one chunk dispatch and the
host's wait for its results), in ms."""
from bench import program_spans


def read(ctx):
    w = program_spans.window(ctx)
    return None if w is None else w.median_ms("node.mine_block",
                                              "executor.run_full.chunk")
