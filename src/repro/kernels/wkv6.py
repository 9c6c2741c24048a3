"""Pallas TPU kernel: fused RWKV-6 WKV recurrence.

    o_t = r_t^T (S_{t-1} + (u * k_t) v_t^T);   S_t = Diag(w_t) S_{t-1} + k_t v_t^T

TPU adaptation: CUDA RWKV kernels assign one thread per (batch, head,
channel); here the matrix-valued state S (K x V) lives in a VMEM scratch
accumulator, each time step is a rank-1 update (outer product on the
VPU/MXU), and the grid iterates (B*H) with r/k/v/w streamed through VMEM
in sequence-chunks.  Fusing the whole recurrence avoids materializing
the (B, S, H, K, V) intermediate a parallel-scan formulation would need —
the HBM-traffic win that makes linear attention worthwhile on TPU.

Grid: (B*H,).  ops.py chunks the sequence and carries S across calls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref):
    """r,k,w: (1, S, K); v: (1, S, V); u: (1, K); s0: (1, K, V)."""
    S = r_ref.shape[1]
    u = u_ref[0, :]                                        # (K,)

    def step(t, s):                                        # s: (K, V) f32
        rt = r_ref[0, t, :]
        kt = k_ref[0, t, :]
        vt = v_ref[0, t, :]
        wt = w_ref[0, t, :]
        kv = kt[:, None] * vt[None, :]                     # (K, V)
        o_ref[0, t, :] = (rt[:, None] * (s + u[:, None] * kv)).sum(axis=0)
        return wt[:, None] * s + kv

    sT = jax.lax.fori_loop(0, S, step, s0_ref[0, :, :])
    sT_ref[0, :, :] = sT


def wkv6_pallas(r, k, v, w, u, s0, *, interpret: bool):
    """r,k,w: (BH, S, K); v: (BH, S, V); u: (BH, K); s0: (BH, K, V)
    -> (o (BH, S, V), sT (BH, K, V)), all float32."""
    BH, S, K = r.shape
    V = v.shape[-1]
    return pl.pallas_call(
        _wkv6_kernel,
        grid=(BH,),
        in_specs=[
            pl.BlockSpec((1, S, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, S, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, S, V), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, S, K), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, K), lambda i: (i, 0)),
            pl.BlockSpec((1, K, V), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, V), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, K, V), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, V), jnp.float32),
            jax.ShapeDtypeStruct((BH, K, V), jnp.float32),
        ],
        interpret=interpret,
    )(r, k, v, w, u, s0)
