"""Median over the window's heights of the ``ledger.merkle_root`` time
under ``workload.verify`` of both nodes (each re-derives the block's
root with hashlib from the raw ``arg || res`` leaves), in ms."""
from bench import height_spans


def read(ctx):
    return height_spans.median_ms(ctx, "ledger.merkle_root",
                                  within="workload.verify")
