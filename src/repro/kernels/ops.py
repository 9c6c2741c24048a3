"""Jitted public wrappers around the Pallas kernels, with batch padding,
sequence chunking, and a pure-jnp path.

This module is the one place that decides how a Pallas kernel runs:
``backend="pallas"`` compiles the kernel for the TPU unless the caller
asks for the Pallas interpreter by name (``interpret=True``, what the CPU
tests do).  Nothing falls back to interpret mode on its own, so a kernel
on the chip path never runs interpreted by accident.  The pure-jnp
reference path (``backend="jnp"``) is what the model forward uses by
default so the 512-device dry-run lowers to plain HLO (DESIGN.md §5).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.decay_scan import TILE_C, decay_scan_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.sha256 import TILE_N, sha256_pallas
from repro.kernels.wkv6 import wkv6_pallas


# ---------------------------------------------------------------------------
# sha256
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("backend", "interpret"))
def sha256_words(msg: jax.Array, backend: str = "jnp",
                 interpret: bool = False) -> jax.Array:
    """msg: uint32 (N, W) -> (N, 8) digests.  backend: "jnp" | "pallas"."""
    if backend == "jnp":
        return _ref.sha256_words_ref(msg)
    padded = _ref.sha256_pad_words(msg)
    N = padded.shape[0]
    pad_n = (-N) % TILE_N
    if pad_n:
        padded = jnp.concatenate(
            [padded, jnp.zeros((pad_n, padded.shape[1]), jnp.uint32)], axis=0)
    out = sha256_pallas(padded, interpret=interpret)
    return out[:N]


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "backend", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, backend: str = "jnp",
                    bq: int = 512, bk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B, S, H, hd); k, v: (B, T, Kv, hd) -> (B, S, H, hd).

    GQA: kv heads are broadcast to H inside the fold.  backend "jnp"
    delegates to the query-chunked model reference."""
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    if backend == "jnp":
        from repro.models.attention import chunked_attention
        return chunked_attention(q, k, v, causal=causal)
    G = H // Kv
    kx = jnp.repeat(k, G, axis=2) if G > 1 else k
    vx = jnp.repeat(v, G, axis=2) if G > 1 else v
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], hd)
    out = flash_attention_pallas(fold(q), fold(kx), fold(vx),
                                 causal=causal, bq=bq, bk=bk,
                                 interpret=interpret)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# decay scan
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("backend", "seq_chunk",
                                             "interpret"))
def decay_scan(a: jax.Array, b: jax.Array, h0: jax.Array | None = None,
               backend: str = "jnp", seq_chunk: int = 2048,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """h_t = a_t h_{t-1} + b_t.  a, b: (B, S, C).  Returns (h, h_last)."""
    B, S, C = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, C), a.dtype)
    if backend == "jnp":
        h = _ref.decay_scan_ref(a, b, h0)
        return h, h[:, -1]
    pad_c = (-C) % TILE_C
    if pad_c:
        z = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad_c)])
        a, b, h0 = z(a), z(b), z(h0)
    outs = []
    h = h0
    for s0 in range(0, S, seq_chunk):
        sl = slice(s0, min(s0 + seq_chunk, S))
        o, h = decay_scan_pallas(a[:, sl], b[:, sl], h,
                                 interpret=interpret)
        outs.append(o)
    out = jnp.concatenate(outs, axis=1)[..., :C]
    return out, h[..., :C]


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("backend", "seq_chunk",
                                             "interpret"))
def wkv6(r, k, v, w, u, s0=None, backend: str = "jnp",
         seq_chunk: int = 1024, interpret: bool = False):
    """r,k,w: (B,S,H,K); v: (B,S,H,V); u: (H,K); s0: (B,H,K,V).
    Returns (out (B,S,H,V) f32, s_final f32)."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    if s0 is None:
        s0 = jnp.zeros((B, H, K, V), jnp.float32)
    if backend == "jnp":
        return _ref.wkv6_ref(r, k, v, w, u, s0)
    fold = lambda x: x.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        B * H, S, x.shape[-1])
    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)
    uf = jnp.broadcast_to(u.astype(jnp.float32), (B, H, K)).reshape(B * H, K)
    sf = s0.astype(jnp.float32).reshape(B * H, K, V)
    outs = []
    for c0 in range(0, S, seq_chunk):
        sl = slice(c0, min(c0 + seq_chunk, S))
        o, sf = wkv6_pallas(rf[:, sl], kf[:, sl], vf[:, sl], wf[:, sl],
                            uf, sf, interpret=interpret)
        outs.append(o)
    out = jnp.concatenate(outs, axis=1)
    out = out.reshape(B, H, S, V).transpose(0, 2, 1, 3)
    return out, sf.reshape(B, H, K, V)
