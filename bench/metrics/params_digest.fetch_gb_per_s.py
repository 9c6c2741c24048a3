"""Bytes fetched from the device to the host by ``params_digest``
(``d2h_bytes`` of the ``tree_digest.fetch`` spans under it, under the
window's ``node.mine_block`` spans) over the seconds of those spans, in
GB/s."""
from bench import program_spans


def read(ctx):
    w = program_spans.window(ctx)
    return None if w is None else w.gb_per_s(
        "node.mine_block", "tree_digest.fetch", "d2h_bytes",
        within="params_digest")
