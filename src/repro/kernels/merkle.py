"""Batched Merkle-tree reduction on a vectorized SHA-256 (DESIGN.md §6).

The block-commitment hot path: Bitcoin-style Merkle trees (duplicate the
last node on odd levels) computed level-by-level with a batched SHA-256
compression instead of per-leaf ``hashlib`` calls.  All wide levels of a
tree are traced into one jitted function — a root over N leaves is ONE
device dispatch doing ~2N compressions across lanes instead of 2N
Python-interpreter round-trips.

Three implementation choices matter for throughput:

- **words-major layout**: the level lives as 8 contiguous rows of width n
  (one row per digest word), so every round's vector ops stream over
  contiguous lanes and LLVM/Mosaic can actually vectorize them.
- **constant padding schedule**: an interior node hashes a 64-byte
  message, so its second compression block is the *fixed* SHA-256 padding
  block; its message schedule (and ``K[t] + W[t]``) is precomputed into
  the ``_KW`` table, cutting that compression's op count by ~40%.
- **hybrid cutover**: below ``_CUTOVER`` lanes the per-op dispatch cost
  exceeds the hashing cost, so the narrow top of the tree finishes on the
  host with ``hashlib`` — bit-identical either way.

Word convention: SHA-256 serializes uint32 words big-endian, and digests
are big-endian words — so an internal node over two child digests is just
their 16 words concatenated, and a byte string of length 4k hashes
identically to its ``>u4`` word view.  ``bswap32`` converts little-endian
word buffers (e.g. ``np.uint32.tobytes()`` leaves built by the executor)
into this convention in-kernel.
"""
from __future__ import annotations

import functools
import hashlib
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import sha256_words
from repro.kernels.ref import _H0, _K

# Tree levels narrower than this run on the host: at ~64 lanes the
# fixed per-op dispatch cost of the traced compression exceeds hashlib's
# per-call cost (measured in BENCH_pipeline.json).
_CUTOVER = 64


def bswap32(x: jax.Array) -> jax.Array:
    """Byte-swap each uint32 lane (little-endian words -> big-endian)."""
    x = x.astype(jnp.uint32)
    return ((x << jnp.uint32(24))
            | ((x & jnp.uint32(0xFF00)) << jnp.uint32(8))
            | ((x >> jnp.uint32(8)) & jnp.uint32(0xFF00))
            | (x >> jnp.uint32(24)))


# ---------------------------------------------------------------------------
# packing: bytes <-> big-endian word arrays
# ---------------------------------------------------------------------------


def pack_leaves(leaves: Sequence[bytes]) -> Optional[np.ndarray]:
    """Uniform word-aligned leaves -> (N, L//4) big-endian uint32 words.

    Returns None when the leaf set is ragged or not 4-byte aligned (the
    caller then falls back to hashlib for the leaf level only)."""
    if not leaves:
        return None
    L = len(leaves[0])
    if L == 0 or L % 4 or any(len(x) != L for x in leaves):
        return None
    buf = b"".join(leaves)
    return np.frombuffer(buf, dtype=">u4").reshape(len(leaves), L // 4) \
        .astype(np.uint32)


def pack_digests(digests: Sequence[bytes]) -> np.ndarray:
    """32-byte digests -> (N, 8) uint32 word rows."""
    return np.frombuffer(b"".join(digests), dtype=">u4").reshape(-1, 8) \
        .astype(np.uint32)


def words_to_hex(words: np.ndarray) -> str:
    """(8,) uint32 digest words -> hex string (big-endian serialization)."""
    return np.asarray(words, np.uint32).astype(">u4").tobytes().hex()


def _words_to_digest_list(level: np.ndarray) -> List[bytes]:
    buf = np.ascontiguousarray(level.astype(">u4")).tobytes()
    return [buf[i:i + 32] for i in range(0, len(buf), 32)]


# ---------------------------------------------------------------------------
# vectorized SHA-256 compression, words-major
# ---------------------------------------------------------------------------


def _pad_block_schedule() -> List[int]:
    """Message schedule of the constant padding block of a 64-byte msg."""
    w = [0x80000000] + [0] * 14 + [512]

    def rr(x, n):
        return ((x >> n) | (x << (32 - n))) & 0xFFFFFFFF

    for t in range(16, 64):
        s0 = rr(w[t - 15], 7) ^ rr(w[t - 15], 18) ^ (w[t - 15] >> 3)
        s1 = rr(w[t - 2], 17) ^ rr(w[t - 2], 19) ^ (w[t - 2] >> 10)
        w.append((w[t - 16] + s0 + w[t - 7] + s1) & 0xFFFFFFFF)
    return w


# K[t] + W[t] folded into one constant per round of the padding block
_KW = np.array([(int(k) + w) & 0xFFFFFFFF
                for k, w in zip(_K, _pad_block_schedule())], np.uint32)


def _rotr(x, n):
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _round(s, kw):
    a, b, c, d, e, f, g, h = s
    S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
    ch = g ^ (e & (f ^ g))
    t1 = h + S1 + ch + kw
    S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
    maj = (a & b) | (c & (a | b))
    return (t1 + S0 + maj, a, b, c, d + t1, e, f, g)


def _node_hash(w16):
    """SHA-256 of 64-byte messages given as 16 word rows of (n,) lanes.

    The 64 rounds of each block are a ``fori_loop``, not unrolled: XLA
    compiles a fully unrolled 128-round chain per tree level in minutes,
    and a whole tree's worth of them not at all."""
    n = w16[0].shape[0]
    init = tuple(jnp.full((n,), h, jnp.uint32) for h in _H0)
    k, kw = jnp.asarray(_K), jnp.asarray(_KW)

    # block 1: the message, rolling 16-word schedule (w[0] is W[t])
    def msg_round(t, carry):
        s, w = carry
        s = _round(s, w[0] + k[t])
        s0 = _rotr(w[1], 7) ^ _rotr(w[1], 18) ^ (w[1] >> jnp.uint32(3))
        s1 = _rotr(w[14], 17) ^ _rotr(w[14], 19) ^ (w[14] >> jnp.uint32(10))
        return s, w[1:] + (w[0] + s0 + w[9] + s1,)

    s, _ = jax.lax.fori_loop(0, 64, msg_round, (init, tuple(w16)))
    mid = tuple(x + y for x, y in zip(init, s))
    # block 2: constant padding, precomputed K+W schedule
    s = jax.lax.fori_loop(0, 64, lambda t, s: _round(s, kw[t]), mid)
    return tuple(x + y for x, y in zip(mid, s))


# Bounded: each entry is an executable compiled per leaf
# count (static shapes are what make the dispatch fast); the bound keeps a
# workload with many distinct block sizes from accumulating executables
# forever.
@functools.lru_cache(maxsize=32)
def _tree_fn(n: int, keep_levels: bool):
    """Jitted device reduction of an (8, n) words-major digest level down
    to width <= ``_CUTOVER``.  Levels are traced one after another (the
    tree shape is static).  Root path returns only the boundary level; with
    ``keep_levels`` every intermediate level comes back already odd-padded
    — exactly the rows a proof's sibling lookup indexes into — except the
    last (the host continues from it)."""

    def reduce(rows8):
        rows = [rows8[i] for i in range(8)]      # contiguous (n,) lanes
        width, levels = n, []
        while width > _CUTOVER:
            if width % 2:
                rows = [jnp.concatenate([r, r[-1:]]) for r in rows]
                width += 1
            levels.append(rows)
            pairs = [r[0::2] for r in rows] + [r[1::2] for r in rows]
            rows = list(_node_hash(pairs))
            width //= 2
        levels.append(rows)
        if not keep_levels:
            levels = levels[-1:]
        return tuple(jnp.stack(lv) for lv in levels)     # (8, m) each

    return jax.jit(reduce)


# ---------------------------------------------------------------------------
# the hybrid tree
# ---------------------------------------------------------------------------


def _host_levels(digests: List[bytes]) -> List[List[bytes]]:
    """Reference tail: hashlib over a pre-joined buffer, one level a pass."""
    levels, level = [], list(digests)
    sha = hashlib.sha256
    while len(level) > 1:
        if len(level) % 2:
            level.append(level[-1])
        levels.append(level)
        buf = b"".join(level)
        level = [sha(buf[i:i + 64]).digest() for i in range(0, len(buf), 64)]
    levels.append(level)
    return levels


def _hybrid_levels(digests: np.ndarray, *,
                   keep_levels: bool = True) -> Tuple[List[np.ndarray], str]:
    """(N, 8) leaf digests -> (padded levels as (m, 8) arrays, root hex)."""
    n = int(digests.shape[0])
    if n == 0:
        return [], hashlib.sha256(b"").hexdigest()
    device_levels: List[np.ndarray] = []
    if n > _CUTOVER:
        rows8 = jnp.asarray(
            np.ascontiguousarray(np.asarray(digests, np.uint32).T))
        out = _tree_fn(n, keep_levels)(rows8)
        device_levels = [np.asarray(lv).T for lv in out[:-1]]
        boundary = _words_to_digest_list(np.asarray(out[-1]).T)
    else:
        boundary = _words_to_digest_list(np.asarray(digests, np.uint32))
    host = _host_levels(boundary)
    levels = device_levels + [pack_digests(lv) for lv in host]
    return levels, host[-1][0].hex()


def leaf_digests_device(packed: np.ndarray | jax.Array) -> jax.Array:
    """(N, W) big-endian word leaves -> (N, 8) leaf digests on device."""
    return sha256_words(jnp.asarray(packed, jnp.uint32))


def _digests_for(leaves: Sequence[bytes]) -> np.ndarray:
    packed = pack_leaves(leaves)
    if packed is not None and len(leaves) >= _CUTOVER:
        return np.asarray(leaf_digests_device(packed))
    return pack_digests([hashlib.sha256(x).digest() for x in leaves])


def merkle_root_from_digests(digests: np.ndarray | jax.Array) -> str:
    """(N, 8) uint32 leaf-digest words -> root hex."""
    return _hybrid_levels(np.asarray(digests), keep_levels=False)[1]


# Bounded like ``_tree_fn``: one executable per per-block leaf count
# (the batch dimension is specialized inside jax.jit).  Levels stop at
# ``_CUTOVER`` per-block width exactly as in ``_tree_fn``, and the narrow
# tops finish on the host.
@functools.lru_cache(maxsize=32)
def _forest_fn(width: int):
    """Jitted reduction of a *forest*: (8, B, W) words-major digest
    levels down to per-block width <= ``_CUTOVER``, every wide level of
    every tree in one dispatch.  Pairing happens within each block's
    lanes (odd levels duplicate the block's own last node), so each of
    the B trees is reduced exactly as ``_tree_fn`` would reduce it
    alone — but the compression runs over B * w/2 lanes at once, which
    is what keeps the device busy when the segment is long."""

    def reduce(rows8):
        rows = [rows8[i] for i in range(8)]          # (B, w) each
        w = width
        while w > _CUTOVER:
            if w % 2:
                rows = [jnp.concatenate([r, r[:, -1:]], axis=1)
                        for r in rows]
                w += 1
            pairs = [r[:, 0::2].reshape(-1) for r in rows] \
                + [r[:, 1::2].reshape(-1) for r in rows]
            out = _node_hash(pairs)                  # (B * w/2,) lanes
            rows = [o.reshape(rows8.shape[1], -1) for o in out]
            w //= 2
        return jnp.stack(rows)                       # (8, B, w)

    return jax.jit(reduce)


def merkle_roots_from_digests(digests: np.ndarray | jax.Array
                              ) -> List[str]:
    """(B, N, 8) uint32 leaf-digest words -> B root hex strings.

    The batched analogue of ``merkle_root_from_digests``: B same-shaped
    trees reduced together, all wide levels in one jitted dispatch,
    then B narrow tops (<= ``_CUTOVER`` digests each) finished on the
    host.  Bit-identical per block to the single-tree reducers."""
    d = np.asarray(digests, np.uint32)
    if d.ndim != 3 or d.shape[-1] != 8:
        raise ValueError(f"expected (B, N, 8) digest words, got {d.shape}")
    B, n, _ = d.shape
    if B == 0:
        return []
    if n == 0:
        return [hashlib.sha256(b"").hexdigest()] * B
    if n > _CUTOVER:
        rows8 = jnp.asarray(np.ascontiguousarray(d.transpose(2, 0, 1)))
        d = np.asarray(_forest_fn(n)(rows8)).transpose(1, 2, 0)
    return [_host_levels(_words_to_digest_list(d[b]))[-1][0].hex()
            for b in range(B)]


def merkle_root_device(leaves: Sequence[bytes]) -> str:
    """Device analogue of ``core.ledger.merkle_root`` — bit-identical."""
    if not leaves:
        return hashlib.sha256(b"").hexdigest()
    return merkle_root_from_digests(_digests_for(leaves))


def merkle_levels_device(leaves: Sequence[bytes]) -> List[np.ndarray]:
    """All (odd-padded) tree levels, leaf digests first, root level last."""
    return _hybrid_levels(_digests_for(leaves))[0]


def merkle_proof_device(leaves: Sequence[bytes], index: int) -> List[dict]:
    """Inclusion proof in the ``core.ledger`` format, tree built on device.

    Raises ``IndexError`` for an index outside the leaf set — a proof
    over a duplicated odd-level pad node would verify against the root
    without corresponding to any submitted result."""
    if not 0 <= index < len(leaves):
        raise IndexError(
            f"proof index {index} out of range for {len(leaves)} leaves")
    levels = merkle_levels_device(leaves)
    proof = []
    idx = index
    for level in levels[:-1]:
        sib = idx ^ 1
        proof.append({"side": "left" if sib < idx else "right",
                      "hash": words_to_hex(level[sib])})
        idx //= 2
    return proof
