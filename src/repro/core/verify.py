"""Result verification: the consensus stand-in (DESIGN.md §2, §10).

PNPCoin requires jash determinism "across runs, architectures, and
compilations" (§3 req. 2) — that is what lets any node audit any miner.
``quorum_verify`` re-executes a random fraction of the arg space on
verifier devices and compares digests bit-exactly; one mismatch marks the
block invalid.  ``verify_inclusion`` checks a single (arg, res) pair
against the block's Merkle root — the light-client path.

Because every peer re-verifies every mined block (§3.3), verification —
not mining — dominates network compute at scale.  The batched
counterparts amortize it across a chain segment:

* ``quorum_verify_batched`` stacks every block's sampled args into one
  cached jitted dispatch per distinct jash function (identical
  per-block sampling, so accept/reject is bit-identical to N calls of
  ``quorum_verify``);
* ``recompute_roots_batched`` re-commits every block's Merkle root
  independently from its raw ``(arg, res)`` arrays on the words-major
  device reducer (one fused leaf-digest dispatch + one forest
  reduction), with a ``hashlib`` spot-check of one block's root per
  shape group — the reference code path stays exercised against every
  shape-specialized kernel used, and a spot-check mismatch falls back
  to recomputing *every* root with ``hashlib`` so the accept/reject
  decision never depends on the device kernel.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.executor import FullResult, _as_words
from repro.core.jash import Jash
from repro.core.ledger import merkle_proof, merkle_root, verify_merkle_proof
from repro.kernels.merkle import bswap32, merkle_roots_from_digests
from repro.kernels.ops import sha256_words


@dataclasses.dataclass(frozen=True)
class VerifyReport:
    n_checked: int
    n_mismatch: int
    ok: bool
    mismatched_args: tuple = ()


@functools.lru_cache(maxsize=128)
def _recompute_fn(jash_fn):
    """Compiled subset re-executor, cached on the jash function so every
    audit of the same jash (a network's worth of receive-side verifies)
    reuses one executable instead of re-jitting per call."""
    return jax.jit(jax.vmap(lambda a: _as_words(jash_fn(a))))


def _sample_indices(n: int, *, fraction: float, seed: int,
                    min_checks: int) -> np.ndarray:
    """The quorum sample for one block — shared by the scalar and
    batched paths so their accept/reject decisions are bit-identical."""
    rng = np.random.RandomState(seed)
    k = max(min_checks, int(n * fraction))
    return rng.choice(n, size=min(k, n), replace=False)


def quorum_verify(jash: Jash, full: FullResult, *, fraction: float = 0.05,
                  seed: int = 0, min_checks: int = 4) -> VerifyReport:
    """Deterministic re-execution of a random subset of args."""
    with spans.span("verify.quorum_verify"):
        idx = _sample_indices(len(full.args), fraction=fraction, seed=seed,
                              min_checks=min_checks)

        args = jnp.asarray(full.args[idx], jnp.uint32)
        recomputed = np.asarray(_recompute_fn(jash.fn)(args))

        mism = [int(full.args[i]) for j, i in enumerate(idx)
                if not np.array_equal(recomputed[j], full.results[i])]
        return VerifyReport(n_checked=len(idx), n_mismatch=len(mism),
                            ok=not mism, mismatched_args=tuple(mism))


def quorum_verify_batched(pairs: Sequence[Tuple[Jash, FullResult]], *,
                          fraction: float = 0.05, seed: int = 0,
                          min_checks: int = 4) -> List[VerifyReport]:
    """``quorum_verify`` over a chain segment in one dispatch per jash.

    Each block samples exactly the indices its scalar call would (same
    seeded draw), then all sampled args of blocks sharing a jash
    function are stacked into a single cached jitted re-execution —
    padded up to a power of two so segment lengths don't accumulate
    executables.  Reports are bit-identical to per-block
    ``quorum_verify`` calls."""
    samples = [
        _sample_indices(len(full.args), fraction=fraction, seed=seed,
                        min_checks=min_checks)
        for _, full in pairs]
    by_fn: dict = {}
    for b, (jash, _) in enumerate(pairs):
        by_fn.setdefault(jash.fn, []).append(b)

    recomputed: List[Optional[np.ndarray]] = [None] * len(pairs)
    for fn, blocks in by_fn.items():
        stacked = np.concatenate(
            [pairs[b][1].args[samples[b]] for b in blocks])
        total = len(stacked)
        padded_n = 1 << max(total - 1, 1).bit_length()
        padded = np.zeros(padded_n, np.uint32)
        padded[:total] = stacked
        out = np.asarray(_recompute_fn(fn)(jnp.asarray(padded)))[:total]
        off = 0
        for b in blocks:
            k = len(samples[b])
            recomputed[b] = out[off:off + k]
            off += k

    reports = []
    for b, (_, full) in enumerate(pairs):
        idx, out = samples[b], recomputed[b]
        expect = full.results[idx]      # same indexing as the scalar path
        bad = ~(out.reshape(len(idx), -1) == expect.reshape(len(idx), -1)
                ).all(axis=1)
        mism = tuple(int(full.args[i]) for i in idx[bad])
        reports.append(VerifyReport(n_checked=len(idx),
                                    n_mismatch=len(mism), ok=not mism,
                                    mismatched_args=mism))
    return reports


def recompute_roots_batched(fulls: Sequence[FullResult], *,
                            seed: int = 0) -> List[str]:
    """Independent Merkle-root re-commitment for a segment of blocks.

    Re-derives each block's root from its raw ``(arg, res)`` arrays —
    never trusting the evidence ``leaf_digests`` — via one fused
    device leaf-digest dispatch and one forest reduction per distinct
    block shape.  One seeded-random block per shape group is
    additionally re-committed end-to-end with ``hashlib`` (the
    reference path, exercised for every shape-specialized kernel this
    call used); a mismatch there means the device kernel disagrees
    with the reference, and *every* root is then recomputed with
    ``hashlib`` so batched accept/reject stays bit-identical to the
    per-block path."""
    if not fulls:
        return []
    packed = [full.packed_words() for full in fulls]
    by_shape: dict = {}
    for b, words in enumerate(packed):
        by_shape.setdefault(words.shape, []).append(b)

    roots: List[Optional[str]] = [None] * len(fulls)
    for shape, blocks in by_shape.items():
        words = np.stack([packed[b] for b in blocks])
        flat = jnp.asarray(words.reshape(-1, shape[1]), jnp.uint32)
        digests = np.asarray(sha256_words(bswap32(flat))) \
            .reshape(len(blocks), shape[0], 8)
        for b, root in zip(blocks, merkle_roots_from_digests(digests)):
            roots[b] = root

    # hashlib spot-check of one root per *shape group*: each group took
    # its own device path (leaf width and forest executable are shape-
    # specialized), so probing one member per group keeps the distinct
    # reference code path live on every kernel actually used this call,
    # catching a device regression on real traffic instead of only in
    # tests
    rng = np.random.RandomState(seed)
    for blocks in by_shape.values():
        probe = blocks[int(rng.randint(len(blocks)))]
        reference = merkle_root(list(fulls[probe].merkle_leaves),
                                backend="hashlib")
        if reference != roots[probe]:        # device kernel is wrong:
            return [merkle_root(list(f.merkle_leaves), backend="hashlib")
                    for f in fulls]          # fall back to the reference
    return roots


def verify_inclusion(full: FullResult, arg_index: int, root: str) -> bool:
    """Merkle inclusion proof for one submitted result.

    Raises ``IndexError`` for an index outside the block's arg space —
    there is no leaf (and hence no meaningful proof) to check."""
    if not 0 <= arg_index < len(full.args):
        raise IndexError(
            f"arg_index {arg_index} out of range for a block of "
            f"{len(full.args)} results")
    leaves = list(full.merkle_leaves)
    proof = merkle_proof(leaves, arg_index)
    return verify_merkle_proof(leaves[arg_index], proof, root)
