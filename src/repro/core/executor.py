"""Execution engines: how miners (mesh devices) evaluate a published jash
over its argument space (PNPCoin §3.3).

**full** mode — "Full execution returns the output of every valid input":
the arg space [0, n_args) is processed in fixed-size chunks; each chunk is
one jitted ``shard_map`` dispatch that fuses jash eval, the submission
hash ``sha256(arg || res)``, and the Merkle *leaf digest*
``sha256(arg_bytes || res_bytes)`` (the batched SHA-256 kernel runs both).
Chunking bounds device memory for large ``n_args`` — only one chunk of
results is ever resident on device — and every chunk reuses the same
compiled executable.  The block commitment (Merkle root over all leaf
digests) is a single fused device reduction (``kernels/merkle``).

**optimal** mode — "accepts the lowest res, the result with most leading
zeros": each miner reduces its slice to the lexicographic (res, arg)
minimum in a single vectorized pass (min + tie-masked min + argmax — no
O(n log n) sort), and a global gather-min picks the block winner.

**multi-lane mining** — ``lanes=k`` emulates a k-miner fleet on one
device: the arg space is partitioned over k miner lanes and the whole
fleet runs as one vmapped dispatch (full mode: a strided
``(width, lanes)`` re-tile inside the fused chunk executor, so
``miner_of = arg % lanes`` attribution matches the mesh convention;
optimal mode: contiguous per-lane slices, each reduced to its
lexicographic minimum, with a cross-lane argmin picking the winner
lane).  Lane partitioning never changes the mined bits: full-mode
results/hashes and the optimal ``(best_arg, best_res)`` are bit-identical
to ``lanes=1`` — contiguous optimal slices preserve the global
first-occurrence tie-break — which is what lets a verifier replay with
``lanes=1`` and still match a multi-lane miner's commitment exactly.

On the CPU container the same code runs on a 1-device mesh; on the
production mesh the miner axis is ("data",) (256 miners/pod) or
("pod", "data") (512).  ``lanes`` and a sharded mesh are mutually
exclusive: a real fleet already has its miner axes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import spans
from repro.core.jash import Jash
from repro.kernels.merkle import bswap32, merkle_root_from_digests
from repro.kernels.ops import sha256_words

# Default ceiling on per-dispatch rows in full mode: bounds device-resident
# results while keeping each dispatch large enough to stay kernel-bound.
DEFAULT_CHUNK = 1 << 16


@dataclasses.dataclass(frozen=True)
class FullResult:
    args: np.ndarray           # (n,) uint32
    results: np.ndarray        # (n, res_words) uint32
    hashes: np.ndarray         # (n, 8) uint32  sha256(arg || res)
    miner_of: np.ndarray       # (n,) int32 — first submitter per arg
    leaf_digests: np.ndarray   # (n, 8) uint32  sha256(leaf bytes)
    _leaves: Optional[Tuple[bytes, ...]] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    _packed: Optional[np.ndarray] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def packed_words(self) -> np.ndarray:
        """(n, 1 + res_words) little-endian uint32 message words — each
        row is the ``arg || res`` Merkle-leaf message.  This is the array
        the fused executor hashes in-dispatch (after an in-kernel
        ``bswap32``) and the batched verifier re-hashes independently;
        ``merkle_leaves`` is its byte view.  Cached: batched
        verification reads it once for the dedup key and once for the
        root recompute."""
        if self._packed is None:
            object.__setattr__(self, "_packed", np.ascontiguousarray(
                np.concatenate([self.args[:, None], self.results],
                               axis=1).astype("<u4")))
        return self._packed

    @property
    def merkle_leaves(self) -> Tuple[bytes, ...]:
        """Leaf byte strings ``arg.tobytes() + res.tobytes()``, materialized
        lazily from the packed arrays (one buffer slice per leaf, no per-row
        ``tobytes`` loop)."""
        if self._leaves is None:
            packed = self.packed_words()
            buf = packed.tobytes()
            stride = packed.shape[1] * 4
            leaves = tuple(buf[i * stride:(i + 1) * stride]
                           for i in range(packed.shape[0]))
            object.__setattr__(self, "_leaves", leaves)
        return self._leaves

    def commit_root(self) -> str:
        """Block-commitment Merkle root over the leaf digests (device)."""
        return merkle_root_from_digests(self.leaf_digests)


@dataclasses.dataclass(frozen=True)
class OptimalResult:
    best_arg: int
    best_res: np.ndarray       # (res_words,) uint32
    winner: int                # miner id
    n_evaluated: int


def _as_words(res) -> jax.Array:
    """Canonicalize a jash result pytree to a flat uint32 vector."""
    leaves = jax.tree.leaves(res)
    flat = [jnp.atleast_1d(x).astype(jnp.uint32).reshape(-1) for x in leaves]
    return jnp.concatenate(flat) if len(flat) > 1 else flat[0]


def _miner_axes(mesh: Optional[Mesh]) -> Tuple[str, ...]:
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


@functools.lru_cache(maxsize=128)
def _chunk_executor(jash_fn: Callable, mesh: Optional[Mesh],
                    axes: Tuple[str, ...], lanes: int = 1):
    """Compiled full-mode chunk dispatcher, cached on the jash function so
    repeated ``run_full`` calls (and all chunks within one) reuse one
    executable instead of re-jitting a fresh closure per call.

    With ``lanes > 1`` (single-device multi-lane mode) the chunk is
    re-tiled to ``(width, lanes)`` and the jash is vmapped over both
    axes: lane ``l`` evaluates exactly the args ``≡ l (mod lanes)`` it is
    credited for (``miner_of = arg % lanes``), and the whole lane fleet
    is still one device dispatch.  Element-wise independence makes the
    outputs bit-identical to the ``lanes=1`` layout."""

    def eval_chunk(args_slice):
        if lanes > 1:
            # strided lane partition: row-major (width, lanes) puts arg
            # a in column a % lanes == its miner lane
            lane_args = args_slice.reshape(-1, lanes)
            res = jax.vmap(jax.vmap(lambda a: _as_words(jash_fn(a))))(
                lane_args)
            res = res.reshape(args_slice.shape[0], -1)
        else:
            res = jax.vmap(lambda a: _as_words(jash_fn(a)))(args_slice)
        msg = jnp.concatenate([args_slice[:, None], res], axis=1)
        hashes = sha256_words(msg)
        # Merkle leaf = little-endian bytes of (arg, res) words; bswap
        # re-expresses them in the kernel's big-endian word convention.
        leaf_digests = sha256_words(bswap32(msg))
        return res, hashes, leaf_digests

    if mesh is not None and axes:
        spec = P(axes)
        # check_vma=False: jash functions are researcher code, and the
        # varying-axes check refuses e.g. a bounded loop whose carry
        # starts from a constant; nothing here relies on the check
        fn = shard_map(eval_chunk, mesh=mesh, in_specs=(spec,),
                       out_specs=(spec, spec, spec), check_vma=False)
    else:
        fn = eval_chunk
    return jax.jit(fn)


def run_full(jash: Jash, *, mesh: Optional[Mesh] = None,
             block_reward: float = 1.0,
             chunk_size: Optional[int] = None,
             lanes: int = 1) -> FullResult:
    """Evaluate every valid arg (§3.3 full mode), ``chunk_size`` rows per
    dispatch (None = whole space in one dispatch, capped at
    ``DEFAULT_CHUNK``).  ``lanes`` partitions the arg space over that
    many single-device miner lanes (one vmapped dispatch; ``miner_of =
    arg % lanes``); results are bit-identical to ``lanes=1``."""
    if chunk_size is not None and chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    n = jash.meta.n_args
    axes = _miner_axes(mesh)
    if axes and lanes != 1:
        raise ValueError(
            "lanes is the single-device miner partition; a sharded mesh "
            "already defines the miner fleet via its axes — use one or "
            "the other")
    lanes = min(lanes, n)
    n_miners = int(np.prod([mesh.shape[a] for a in axes])) if axes else lanes

    chunk = min(n, chunk_size or DEFAULT_CHUNK)
    chunk += -chunk % n_miners                 # dispatch divisible by miners
    n_chunks = -(-n // chunk)

    jitted = _chunk_executor(jash.fn, mesh, axes, lanes)
    ctx = mesh if (mesh is not None and axes) else None

    # the last chunk is right-sized (rounded up to the miner count) so a
    # ragged tail doesn't evaluate and hash a whole chunk of discarded args
    tail = n - (n_chunks - 1) * chunk
    tail += -tail % n_miners

    res_parts, hash_parts, leaf_parts = [], [], []
    for c in range(n_chunks):
        with spans.span("executor.run_full.chunk"):
            width = chunk if c < n_chunks - 1 else tail
            args_c = jnp.arange(c * chunk, c * chunk + width,
                                dtype=jnp.uint32)
            if ctx is not None:
                with ctx:
                    r, h, d = jitted(args_c)
            else:
                r, h, d = jitted(args_c)
            res_parts.append(np.asarray(r))
            hash_parts.append(np.asarray(h))
            leaf_parts.append(np.asarray(d))
            spans.count("d2h_bytes", res_parts[-1].nbytes
                        + hash_parts[-1].nbytes + leaf_parts[-1].nbytes)

    cat = (lambda ps: ps[0][:n] if len(ps) == 1
           else np.concatenate(ps, axis=0)[:n])
    res, hashes, leaves = cat(res_parts), cat(hash_parts), cat(leaf_parts)
    args_np = np.arange(n, dtype=np.uint32)
    miner_of = (args_np % n_miners).astype(np.int32) if n_miners > 1 \
        else np.zeros(n, np.int32)
    return FullResult(args=args_np, results=res, hashes=hashes,
                      miner_of=miner_of, leaf_digests=leaves)


# a numpy scalar: a jnp one would start a JAX backend at import
MAXW = np.uint32(0xFFFFFFFF)


def _lex_argmin(w0: jax.Array, w1: jax.Array) -> jax.Array:
    """Index of the lexicographic minimum of (w0, w1) — first occurrence,
    single vectorized pass (three reductions, no sort)."""
    tie = w0 == jnp.min(w0)
    m1 = jnp.min(jnp.where(tie, w1, MAXW))
    # `tie & (w1 == m1)` keeps the edge case where every tied w1 is MAXW
    # from escaping the tie set (a plain argmin over the masked w1 would).
    return jnp.argmax(tie & (w1 == m1))


def _eval_and_reduce(jash_fn: Callable, args_slice, valid_slice):
    """One miner's slice -> its lexicographic (res, arg) minimum, first
    occurrence (three reductions, no sort)."""
    res = jax.vmap(lambda a: _as_words(jash_fn(a)))(args_slice)
    w0 = jnp.where(valid_slice, res[:, 0], MAXW)
    w1 = res[:, 1] if res.shape[1] > 1 else jnp.zeros_like(res[:, 0])
    w1 = jnp.where(valid_slice, w1, MAXW)
    i = _lex_argmin(w0, w1)
    return w0[i], w1[i], args_slice[i], res[i]


@functools.lru_cache(maxsize=128)
def _optimal_executor(jash_fn: Callable, lanes: int):
    """Compiled single-device optimal-mode reducer, cached on the jash
    function (repeated mining/verification replays reuse one executable
    instead of re-jitting a fresh closure per call — the same fix
    ``_chunk_executor`` applies to full mode).

    ``lanes > 1`` vmaps the per-miner reduction over contiguous
    per-lane slices of the arg space in one dispatch; a cross-lane
    lex-argmin then picks the winner lane.  Contiguous slices preserve
    the global first-occurrence tie-break, so ``(best_arg, best_res)``
    is bit-identical for every lane count."""

    def reduce_all(args, valid):
        lane_args = args.reshape(lanes, -1)
        lane_valid = valid.reshape(lanes, -1)
        w0s, w1s, argss, ress = jax.vmap(
            lambda a, v: _eval_and_reduce(jash_fn, a, v))(
                lane_args, lane_valid)
        best = _lex_argmin(w0s, w1s)
        return argss[best], ress[best], best.astype(jnp.int32)

    return jax.jit(reduce_all)


def run_optimal(jash: Jash, *, mesh: Optional[Mesh] = None,
                lanes: int = 1) -> OptimalResult:
    """Distributed argmin of res (§3.3 optimal mode).  The res ordering is
    lexicographic on words == 'most leading zeros' for hash-like outputs.

    ``lanes`` partitions the arg space into that many contiguous
    single-device miner lanes mined in one vmapped dispatch; ``winner``
    is the lane holding the block minimum.  ``(best_arg, best_res)`` is
    independent of the lane count, so a verifier replaying with
    ``lanes=1`` reproduces a multi-lane miner's commitment bit-exactly.
    """
    with spans.span("executor.run_optimal"):
        if lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        n = jash.meta.n_args
        axes = _miner_axes(mesh)

        if mesh is not None and axes:
            if lanes != 1:
                raise ValueError(
                    "lanes is the single-device miner partition; a "
                    "sharded mesh already defines the miner fleet via its "
                    "axes — use one or the other")
            n_miners = int(np.prod([mesh.shape[a] for a in axes]))
            n_pad = -n % n_miners
            args = jnp.arange(n + n_pad, dtype=jnp.uint32)
            valid = args < n

            def sharded(args_all, valid_all):
                w0, w1, arg, res = _eval_and_reduce(jash.fn, args_all,
                                                    valid_all)
                w0g = jax.lax.all_gather(w0, axes)
                w1g = jax.lax.all_gather(w1, axes)
                argsg = jax.lax.all_gather(arg, axes)
                resg = jax.lax.all_gather(res, axes)
                best = _lex_argmin(w0g, w1g)
                return argsg[best], resg[best], best.astype(jnp.int32)

            fn = shard_map(sharded, mesh=mesh, in_specs=(P(axes), P(axes)),
                           out_specs=(P(), P(), P()), check_vma=False)
            with mesh:
                best_arg, best_res, winner = jax.jit(fn)(args, valid)
        else:
            lanes = min(lanes, n)
            n_pad = -n % lanes
            args = jnp.arange(n + n_pad, dtype=jnp.uint32)
            valid = args < n
            best_arg, best_res, winner = _optimal_executor(jash.fn, lanes)(
                args, valid)

        # the host waits here for the device's search
        with spans.span("executor.fetch"):
            return OptimalResult(best_arg=int(best_arg),
                                 best_res=np.atleast_1d(np.asarray(best_res)),
                                 winner=int(winner), n_evaluated=n)
