"""Pallas TPU kernel: flash attention (online softmax).

The roofline (EXPERIMENTS §Roofline) shows attention O(S²) dominating
compute at prefill_32k and its unfused score intermediates dominating the
memory term — exactly the hot spot flash attention removes.  TPU
adaptation: the canonical (batch·heads, q-block, kv-block) grid; the
kv-block dimension is the innermost (sequential) grid axis, so the
running (m, l, acc) state lives in VMEM scratch across kv steps and the
(S, S) score matrix never exists.  Block shapes default to (512, 512)
— MXU-aligned (multiples of 128) with a working set
(BQ·hd + BK·hd + BQ·BK) · 4 B ≈ 1.6 MB, comfortably inside VMEM.

ops.flash_attention handles GQA (kv-head broadcast), scaling, and the
jnp fallback; ref = repro.models.attention.chunked_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, bq: int, bk: int, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:, :] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, :]                                  # (BQ, hd)
    k = k_ref[0, :, :]                                  # (BK, hd)
    v = v_ref[0, :, :]

    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ()))) * scale               # (BQ, BK)

    if causal:
        q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_scr[:]                                   # (BQ,)
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])                     # (BQ, BK)
    l_cur = alpha * l_scr[:] + p.sum(axis=1)
    acc_scr[:, :] = acc_scr[:, :] * alpha[:, None] + \
        p @ v.astype(jnp.float32)
    m_scr[:] = m_cur
    l_scr[:] = l_cur

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0, :, :] = (acc_scr[:, :] /
                          jnp.maximum(l_scr[:], 1e-20)[:, None]
                          ).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 512,
                           bk: int = 512, interpret: bool
                           ) -> jax.Array:
    """q: (BH, S, hd); k, v: (BH, T, hd) -> (BH, S, hd).

    S % bq == 0 and T % bk == 0 (ops pads)."""
    BH, S, hd = q.shape
    T = k.shape[1]
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(_flash_kernel, causal=causal, bq=bq, bk=bk,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(BH, S // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),             # running max m
            pltpu.VMEM((bq,), jnp.float32),             # running sum l
            pltpu.VMEM((bq, hd), jnp.float32),          # accumulator
        ],
        interpret=interpret,
    )(q, k, v)
