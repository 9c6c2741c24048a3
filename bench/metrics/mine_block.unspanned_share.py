"""Share of the window's ``node.mine_block`` time that none of its child
spans covers (its self time over its time, summed over the window's
blocks), in %."""
from bench import program_spans


def _covered(root, children) -> float:
    """Seconds of ``root`` under the union of ``children``."""
    total, reach = 0, root.start_ns
    for c in sorted(children, key=lambda r: r.start_ns):
        a, b = max(c.start_ns, reach), min(c.end_ns, root.end_ns)
        if b > a:
            total += b - a
            reach = b
    return total * 1e-9


def read(ctx):
    w = program_spans.window(ctx)
    if w is None:
        return None
    blocks = w.blocks("node.mine_block")
    spent = sum(map(program_spans.duration, blocks))
    if not blocks or spent <= 0:
        return None
    covered = sum(_covered(b, [r for r, _ in w.under[b.seq]
                               if r.parent == b.seq]) for b in blocks)
    return 100.0 * (spent - covered) / spent
