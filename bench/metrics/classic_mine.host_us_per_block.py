"""Median over the window's blocks of the miner's ``node.mine_block``
time less the time it waits on the device for the search's answer (its
``executor.fetch`` spans), in us: the host's own work per mined
block."""
import statistics

from bench import program_spans


def read(ctx):
    w = program_spans.window(ctx)
    if w is None:
        return None
    blocks = w.blocks("node.mine_block")
    if not blocks:
        return None
    host = [program_spans.duration(b) - sum(map(
        program_spans.duration, w.spans(b, "executor.fetch")))
        for b in blocks]
    return statistics.median(host) * 1e6
