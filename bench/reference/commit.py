"""The commitment formats, written out plainly with ``hashlib``: the
Bitcoin-style Merkle root, and the canonical byte framing the chain
hashes a pytree of arrays with (path | dtype | ndim | shape | data)."""
from __future__ import annotations

import hashlib
from typing import Dict, Iterator, Sequence, Tuple

import numpy as np


def merkle_root(leaves: Sequence[bytes]) -> str:
    """sha256 over each leaf, then pairwise up; an odd level repeats its
    last node."""
    if not leaves:
        return hashlib.sha256(b"").hexdigest()
    level = [hashlib.sha256(x).digest() for x in leaves]
    while len(level) > 1:
        if len(level) % 2:
            level.append(level[-1])
        level = [hashlib.sha256(level[i] + level[i + 1]).digest()
                 for i in range(0, len(level), 2)]
    return level[0].hex()


def flatten(tree: Dict, prefix: str = "") -> Iterator[Tuple[str, np.ndarray]]:
    """(path, leaf) of a nested dict, keys in sorted order."""
    for key in sorted(tree):
        path = f"{prefix}/{key}" if prefix else str(key)
        if isinstance(tree[key], dict):
            yield from flatten(tree[key], path)
        else:
            yield path, tree[key]


def tree_digest(tree: Dict) -> str:
    h = hashlib.sha256()
    for path, leaf in flatten(tree):
        arr = np.ascontiguousarray(np.asarray(leaf))
        if arr.dtype.str.startswith(">"):
            arr = arr.astype(arr.dtype.newbyteorder("<"))
        h.update(path.encode() + b"\x00" + arr.dtype.str.encode() + b"\x00")
        h.update(np.int64(arr.ndim).tobytes())
        h.update(np.asarray(arr.shape, np.int64).tobytes())
        h.update(arr.tobytes(order="C"))
    return h.hexdigest()
