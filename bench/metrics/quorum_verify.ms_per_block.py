"""Median over the window's heights of the ``verify.quorum_verify`` time
of both nodes (the miner's self-check and the verifier's audit: each
re-executes ``verify_fraction`` of the args and compares them row by
row), in ms."""
from bench import height_spans


def read(ctx):
    return height_spans.median_ms(ctx, "verify.quorum_verify")
