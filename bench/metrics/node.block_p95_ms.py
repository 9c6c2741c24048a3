"""95th percentile, over the window's heights, of the time from the
miner's ``node.mine_block`` start to the end of another node's
``node.receive`` of the same height, in ms: ``block_p95_ms`` read from
the program's own spans."""
import statistics

from bench import program_spans


def read(ctx):
    w = program_spans.window(ctx)
    if w is None:
        return None
    received = {}
    for r in w.blocks("node.receive"):
        received.setdefault(r.key["height"], []).append(r)
    times = []
    for m in w.blocks("node.mine_block"):
        ends = [r.end_ns for r in received.get(m.key["height"], ())
                if r.key["node_id"] != m.key["node_id"]]
        if ends:
            times.append((max(ends) - m.start_ns) * 1e-6)
    if len(times) < 20:
        return None
    return statistics.quantiles(times, n=100)[94]
