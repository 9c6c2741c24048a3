"""Pallas TPU kernel: gated linear decay scan  h_t = a_t * h_{t-1} + b_t.

This is the RG-LRU inner recurrence (recurrentgemma).  TPU adaptation:
the GPU way is a warp-level chunked scan; on TPU we tile the *channel*
dimension to the 128-lane VPU and keep the sequential loop over time in
VMEM — sequence chunks stream HBM->VMEM while the carry ``h`` lives in a
VMEM scratch accumulator.  Grid: (B, C // TILE_C); ops.py chunks long
sequences and carries h across calls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_C = 128


def _decay_scan_kernel(a_ref, b_ref, h0_ref, out_ref, hT_ref):
    """a,b,out: (1, S, TILE_C); h0,hT: (1, 1, TILE_C).  Every value stays
    2-D, (1, TILE_C): Mosaic cannot lay out a 1-D loop carry."""
    S = a_ref.shape[1]

    def step(t, h):
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        out_ref[0, row, :] = h
        return h

    hT_ref[0] = jax.lax.fori_loop(0, S, step, h0_ref[0])


def decay_scan_pallas(a: jax.Array, b: jax.Array, h0: jax.Array, *,
                      interpret: bool):
    """a, b: (B, S, C) float32; h0: (B, C) -> (out (B,S,C), hT (B,C)).
    C must be a multiple of TILE_C (ops.py pads)."""
    B, S, C = a.shape
    assert C % TILE_C == 0, C
    grid = (B, C // TILE_C)
    seq = pl.BlockSpec((1, S, TILE_C), lambda i, j: (i, 0, j))
    carry = pl.BlockSpec((1, 1, TILE_C), lambda i, j: (i, 0, j))
    out, hT = pl.pallas_call(
        _decay_scan_kernel,
        grid=grid,
        in_specs=[seq, seq, carry],
        out_specs=[seq, carry],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, C), a.dtype),
            jax.ShapeDtypeStruct((B, 1, C), a.dtype),
        ],
        interpret=interpret,
    )(a, b, h0.reshape(B, 1, C))
    return out, hT.reshape(B, C)
