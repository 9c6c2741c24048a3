"""95th percentile, over every block of the window, of the time from the
miner's ``mine_block`` call to the verifier's ``receive`` returning.  A
block the verifier refused counts as infinitely late; an unbounded tail is
not reported (the run's ``refused`` count fails it)."""
import math
import statistics


def read(ctx):
    times = [(r.end - r.start) * 1e3 if r.accepted else float("inf")
             for r in ctx.records]
    if len(times) < 20:
        return None
    p95 = statistics.quantiles(times, n=100)[94]
    return p95 if math.isfinite(p95) else None
