"""Plain reference of the classic block, in ``hashlib``.

Classic (paper section 3.4): nonce n is hashed as the 8-byte message
``n`` (big-endian uint32) ``|| b"PNPC"``, then the 32-byte digest is
hashed again.  The block's answer is the lowest double hash, compared
as big-endian words (first two words, then the lowest nonce on a tie),
and its Merkle root is over the one leaf ``n`` (little-endian uint32)
``||`` the 8 hash words (little-endian).
"""
from __future__ import annotations

import hashlib
from typing import Tuple

import numpy as np

from bench.reference.commit import merkle_root

SALT = b"PNPC"


def classic(arg_bits: int, rounds: int = 2) -> Tuple[int, np.ndarray, str]:
    """(winning nonce, its hash as 8 uint32 words, one-leaf root).
    ``rounds=1`` is the control: single SHA-256, a broken guarantee."""
    best_key, best = None, None
    for n in range(1 << arg_bits):
        d = n.to_bytes(4, "big") + SALT
        for _ in range(rounds):
            d = hashlib.sha256(d).digest()
        key = d[:8]
        if best_key is None or key < best_key:
            best_key, best = key, (n, d)
    n, d = best
    words = np.frombuffer(d, ">u4").astype(np.uint32)
    leaf = np.uint32(n).tobytes() + words.astype("<u4").tobytes()
    return n, words, merkle_root([leaf])
