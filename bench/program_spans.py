"""The program's own spans (``repro.spans``) inside a run's window, for
the readers of the per-layer metrics that come from them.

The window holds the blocks whose root span (``node.mine_block`` or
``node.receive``) started at or after the first counted block's start and
ended by the last one's end, on the ``perf_counter`` clock of the
harness's ``BlockRecord``s.  A reader gets nothing (``None``) where a
number would come from part of the window only: the ring lost records
of the window, or no root span fell in it; and where the program has no
``repro.spans`` at all."""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, Optional

ROOTS = ("node.mine_block", "node.receive")


class Window:
    """The window's root spans, and each root's descendants with the
    names of the spans between them and the root."""

    def __init__(self, roots: list, under: Dict[int, list]) -> None:
        self.roots = roots
        self.under = under             # root seq -> [(record, ancestors)]

    def blocks(self, root: str) -> list:
        return [r for r in self.roots if r.name == root]

    def spans(self, root_rec, name: str, within: Optional[str] = None):
        return [rec for rec, above in self.under[root_rec.seq]
                if rec.name == name and (within is None or within in above)]

    def median_ms(self, root: str, name: str) -> Optional[float]:
        """Median over the window's ``root`` blocks of the summed time of
        their ``name`` spans, in ms; None where no block has one."""
        per = [self.spans(b, name) for b in self.blocks(root)]
        if not any(per):
            return None
        return statistics.median(sum(map(duration, s)) for s in per) * 1e3

    def gb_per_s(self, root: str, name: str, counter: str,
                 within: str) -> Optional[float]:
        """``counter`` over the seconds of the ``name`` spans under
        ``within`` under the window's ``root`` blocks, in GB/s."""
        found = [s for b in self.blocks(root)
                 for s in self.spans(b, name, within)]
        seconds = sum(map(duration, found))
        if not found or seconds <= 0:
            return None
        return sum((s.counts or {}).get(counter, 0) for s in found) \
            / seconds * 1e-9


def duration(rec) -> float:
    """Seconds of one record."""
    return (rec.end_ns - rec.start_ns) * 1e-9


def window(ctx) -> Optional[Window]:
    try:
        from repro import spans
    except ImportError:                        # a program without spans
        return None
    lo = round(ctx.records[0].start * 1e9)
    hi = round(ctx.records[-1].end * 1e9)
    recs = spans.records()
    # records leave the ring oldest first: the window is whole while the
    # oldest one kept closed before the window began
    if spans.dropped() and (not recs or recs[0].end_ns >= lo):
        return None
    by_seq = {r.seq: r for r in recs}
    roots = [r for r in recs if r.parent is None and r.name in ROOTS
             and lo <= r.start_ns and r.end_ns <= hi]
    if not roots:
        return None
    under: Dict[int, list] = defaultdict(list)
    wanted = {r.seq for r in roots}
    for rec in recs:
        above, node = [], rec
        while node is not None and node.parent is not None:
            node = by_seq.get(node.parent)
            if node is not None:
                above.append(node.name)
        if node is not None and node.seq in wanted and node is not rec:
            under[node.seq].append((rec, above))
    return Window(roots, under)
