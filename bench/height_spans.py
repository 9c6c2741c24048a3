"""Per-block sums of a program span over both nodes of a cell: the
miner's ``node.mine_block`` and every ``node.receive`` of the same
height, for the readers of spans that each node runs once a block (its
audit of the block)."""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Optional

from bench import program_spans


def median_ms(ctx, name: str, within: Optional[str] = None
              ) -> Optional[float]:
    """Median over the window's heights of the summed time of the
    ``name`` spans (under a ``within`` span, if given) of every node's
    root span at that height, in ms; None where no root has one."""
    w = program_spans.window(ctx)
    if w is None:
        return None
    per = defaultdict(float)
    found = False
    for root in w.roots:
        spans = w.spans(root, name, within)
        found = found or bool(spans)
        per[root.key["height"]] += sum(map(program_spans.duration, spans))
    return statistics.median(per.values()) * 1e3 if found else None
