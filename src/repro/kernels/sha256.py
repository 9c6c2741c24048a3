"""Pallas TPU kernel: batched SHA-256 compression.

PNPCoin keeps SHA-256 in two places — "Classic" back-compat blocks (§3.4)
and the full-mode result hashing ("concatenated plain results with hashed
results", §3) — so batched hashing is the one compute hot-spot the paper
itself names.  TPU adaptation (DESIGN.md §2): instead of an ASIC pipeline,
we lane-parallelize — messages lie along the (8, 128) vreg tile, so every
SHA-256 word of ``TILE_N`` independent messages is one full vector
register and each round is a handful of VPU ops over all of them.  The 64
rounds are unrolled in Python with the round constants as immediates (a
traced ``K[t]`` is a dynamic slice, which Mosaic refuses), and the message
schedule is a rolling window of 16 word registers.

Layout: the wrapper transposes ``(N, W)`` messages to ``(W, N/128, 128)``
words-major; grid ``(N // TILE_N,)`` walks (W, 8, 128) message tiles and
(8, 8, 128) digest tiles through VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import _H0, _K

_SUB, _LANE = 8, 128
TILE_N = _SUB * _LANE


def _rotr(x, n):
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _sha256_kernel(k_ref, msg_ref, out_ref, *, nb: int):
    """k_ref: (64,) round constants in SMEM; msg_ref: (nb*16, 8, 128)
    uint32 words; out_ref: (8, 8, 128)."""
    # seed the state through VMEM: a splat constant would enter the round
    # loop with a replicated layout, which Mosaic cannot carry
    for i, h in enumerate(_H0):
        out_ref[i] = jnp.full((_SUB, _LANE), int(h), jnp.uint32)
    state = tuple(out_ref[i] for i in range(8))
    for b in range(nb):
        block = tuple(msg_ref[b * 16 + j] for j in range(16))

        def round_step(t, carry):
            (a, bb, c, d, e, f, g, h), w = carry   # w[0] is W[t]
            S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
            ch = (e & f) ^ (~e & g)
            t1 = h + S1 + ch + k_ref[t] + w[0]
            S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
            maj = (a & bb) ^ (a & c) ^ (bb & c)
            # extend the schedule: W[t+16] = W[t] + s0(W[t+1]) + W[t+9]
            # + s1(W[t+14]), and slide the 16-word window
            s0 = _rotr(w[1], 7) ^ _rotr(w[1], 18) ^ (w[1] >> jnp.uint32(3))
            s1 = (_rotr(w[14], 17) ^ _rotr(w[14], 19)
                  ^ (w[14] >> jnp.uint32(10)))
            w = w[1:] + (w[0] + s0 + w[9] + s1,)
            return (t1 + S0 + maj, a, bb, c, d + t1, e, f, g), w

        s, _ = jax.lax.fori_loop(0, 64, round_step, (state, block))
        state = tuple(st + si for st, si in zip(state, s))
    for i in range(8):
        out_ref[i] = state[i]


def sha256_pallas(padded: jax.Array, *, interpret: bool) -> jax.Array:
    """padded: (N, nb*16) uint32 pre-padded blocks -> (N, 8) digests.

    N must be a multiple of TILE_N (ops.py pads the batch).  ``interpret``
    has no default: ``ops.sha256_words`` passes what its caller asked
    for."""
    N, W = padded.shape
    assert W % 16 == 0
    assert N % TILE_N == 0, N
    words = padded.T.reshape(W, N // _LANE, _LANE)
    out = pl.pallas_call(
        functools.partial(_sha256_kernel, nb=W // 16),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // TILE_N,),
            in_specs=[pl.BlockSpec((W, _SUB, _LANE),
                                   lambda i, k: (0, i, 0))],
            out_specs=pl.BlockSpec((8, _SUB, _LANE),
                                   lambda i, k: (0, i, 0))),
        out_shape=jax.ShapeDtypeStruct((8, N // _LANE, _LANE), jnp.uint32),
        interpret=interpret,
    )(jnp.asarray(_K), words)
    return out.reshape(8, N).T
