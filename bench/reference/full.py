"""Plain reference of a full-mode Collatz block (paper sections 3.2-3.3).

Every arg ``a`` of ``[0, 2**arg_bits)`` is evaluated.  Its result is the
Collatz stopping time of ``max(base + a, 1)`` in uint32 arithmetic
(``base + a`` and ``3b + 1`` wrap modulo 2**32): the number of steps
``b -> b / 2`` (even) or ``b -> 3b + 1`` (odd) until ``b == 1``, or
``max_steps`` where it has not reached 1 within ``max_steps`` steps.
``base`` is the start of the block's slice of the uint32 space.

Byte orders:

* submission hash: ``sha256(arg || res)``, each word big-endian, the
  digest read as 8 big-endian uint32 words;
* Merkle leaf: ``arg || res``, each word little-endian; the root is
  ``commit.merkle_root`` over the leaves in arg order.
"""
from __future__ import annotations

import hashlib
from typing import Tuple

import numpy as np

from bench.reference.commit import merkle_root


def stopping_times(n_args: int, max_steps: int, base: int = 0
                   ) -> np.ndarray:
    """(n_args,) uint32 capped stopping times of ``base + a``, a
    vectorised loop over the args that have not reached 1 yet."""
    res = np.full(n_args, max_steps, np.uint32)
    cur = np.maximum(np.arange(n_args, dtype=np.uint32) + np.uint32(base),
                     np.uint32(1))
    idx = np.arange(n_args)
    at_one = cur == 1
    res[idx[at_one]] = 0
    cur, idx = cur[~at_one], idx[~at_one]
    for step in range(1, max_steps + 1):
        if not len(cur):
            break
        cur = np.where(cur % 2 == 0, cur // np.uint32(2),
                       cur * np.uint32(3) + np.uint32(1))
        at_one = cur == 1
        res[idx[at_one]] = step
        cur, idx = cur[~at_one], idx[~at_one]
    return res


def full(arg_bits: int, max_steps: int, base: int = 0
         ) -> Tuple[np.ndarray, np.ndarray, str]:
    """(results (n, 1) uint32, submission hashes (n, 8) uint32, Merkle
    root hex) of the block over all ``2**arg_bits`` args of the slice
    that starts at ``base``; the hashes and leaves hold the arg ``a``,
    not ``base + a``."""
    n = 1 << arg_bits
    res = stopping_times(n, max_steps, base)
    args = np.arange(n, dtype=np.uint32)
    be = np.stack([args, res], axis=1).astype(">u4").tobytes()
    le = np.stack([args, res], axis=1).astype("<u4").tobytes()
    hashes = np.frombuffer(b"".join(
        hashlib.sha256(be[i:i + 8]).digest() for i in range(0, 8 * n, 8)),
        ">u4").astype(np.uint32).reshape(n, 8)
    root = merkle_root([le[i:i + 8] for i in range(0, 8 * n, 8)])
    return res[:, None], hashes, root
