"""Driver of the full-mode cell: a miner ``Node`` whose chips form the
``("data",)`` miner mesh (``make_host_mesh``) and a one-chip verifier
``Node``, each in its own trust domain (no shared ``VerifyCache``), in
one process.

Every block mines a jash of its own: the Collatz jash over one
``args_per_block`` slice of the uint32 arg space, the slices drawn from
the seed without repeats, as a researcher who publishes the next slice
of the space would.  Before each block the miner ``submit``s that
block's jash to its Runtime Authority and mines it in full mode
(``mine_block("full")``); the block and its payload go to the verifier
as the bytes a peer receives (``encode_payload``/``decode_payload``),
and the verifier audits them from those.  Set-up reviews and compiles
the first ``jashes`` of them, so nothing compiles in the window while
that pool lasts; a block beyond it builds its jash in the window, as
peers that meet a new jash do."""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from bench.harness import BlockRecord, annotate
from bench.lowered_devices import watch
from bench.reference import full as ref

from repro.chain import Node
from repro.chain.store import (decode_block, decode_payload, encode_block,
                               encode_payload)
from repro.chain.workload import ChainError
from repro.core.authority import RuntimeAuthority
from repro.core.executor import DEFAULT_CHUNK, FullResult, run_full
from repro.core.jash import Jash, JashMeta, collatz_jash
from repro.core.verify import quorum_verify
from repro.launch.mesh import make_host_mesh

# the executor's full-mode chunk program, as JAX names it when it lowers
# it (``jit_eval_chunk`` in the trace)
CHUNK_PROGRAM = "jit(eval_chunk)"


def collatz(arg_bits: int, max_steps: int, base: int = 0) -> Jash:
    """The paper's Collatz jash (section 3.2, Figs. 2-3) over the
    ``2**arg_bits`` args of the slice that starts at ``base``: arg ``a``
    gives the capped stopping time of ``base + a``."""
    inner = collatz_jash(max_steps)
    offset = np.uint32(base)

    def fn(arg):
        return inner.fn(arg.astype(np.uint32) + offset)
    return Jash(f"collatz-{base:08x}", fn,
                JashMeta(arg_bits=arg_bits, res_bits=32, importance=0.8,
                         description=f"Collatz stopping times from {base}"),
                example_args=inner.example_args)


class System:
    def __init__(self, cell, seed: int) -> None:
        self.cfg, self.traffic = cell.config, cell.traffic
        self.args_per_block = self.cfg["args_per_block"]
        self.arg_bits = self.args_per_block.bit_length() - 1
        if self.args_per_block != 1 << self.arg_bits:
            raise ValueError("args_per_block must be a power of two")
        self.max_steps = self.traffic["max_steps"]
        self.chips = cell.chips
        self.trace_devices = list(range(cell.chips))
        self.capture_s = 0.0
        self.rng = np.random.default_rng(seed % 2 ** 63)
        # every block's slice of the uint32 space, in the order mined
        self.slices = self.rng.permutation(
            2 ** 32 // self.args_per_block) * self.args_per_block
        self.blocks: List[Dict] = []
        self.lowered = watch()                 # before anything compiles

    def jash(self, i: int) -> Jash:
        return collatz(self.arg_bits, self.max_steps, int(self.slices[i]))

    def setup(self) -> None:
        self.miner = Node(node_id=self.cfg["miner"]["node_id"],
                          mesh=make_host_mesh(),
                          block_reward=self.cfg["block_reward"])
        self.verifier = Node(node_id=self.cfg["verifier"]["node_id"],
                             block_reward=self.cfg["block_reward"])
        for node in (self.miner, self.verifier):
            got = node.workloads["full"].verify_fraction
            if got != self.traffic["verify_fraction"]:
                raise RuntimeError(
                    f"node {node.node_id} audits {got} of the args, the "
                    f"configuration states {self.traffic['verify_fraction']}")
        self.pool = [self.jash(i) for i in range(self.traffic["jashes"])]
        for jash in self.pool:
            self._warm(jash)
        self.block()                           # compiles the rest

    def _warm(self, jash: Jash) -> None:
        """Compile what a block of ``jash`` runs, and compute as little of
        its block as that allows: the Runtime Authority's review (by an
        authority of its own), one chunk of the executor (the block's
        first ``DEFAULT_CHUNK`` args, a sixteenth of a block of 2^20) and
        the quorum re-execution at the block's sample size, on args
        outside the block's range."""
        RuntimeAuthority().submit(jash)
        run_full(dataclasses.replace(
            jash, meta=dataclasses.replace(jash.meta,
                                           max_arg=DEFAULT_CHUNK)),
            mesh=self.miner.mesh)
        n = self.args_per_block
        decoy = np.arange(n, 2 * n, dtype=np.uint32)
        zeros = np.zeros((n, 8), np.uint32)
        quorum_verify(jash, FullResult(
            args=decoy, results=zeros[:, :1], hashes=zeros,
            miner_of=np.zeros(n, np.int32), leaf_digests=zeros),
            fraction=self.traffic["verify_fraction"])

    def block(self) -> BlockRecord:
        t0 = time.perf_counter()
        i = len(self.blocks)
        ok = False
        with annotate("bench.submit"):
            jash = self.pool[i] if i < len(self.pool) else self.jash(i)
            self.miner.submit(jash)
        with annotate("bench.mine_block"):
            try:
                r = self.miner.mine_block("full")
            except ChainError:                 # failed its self-check
                r = None
        entry = {"accepted": False, "base": int(self.slices[i])}
        if r is not None:
            with annotate("bench.wire"):
                blk = decode_block(encode_block(r.record.to_block()))
                payload = decode_payload(encode_payload(r.payload),
                                         {jash.name: jash.fn})
            with annotate("bench.receive"):
                ok = self.verifier.receive(blk, payload,
                                           origin=self.miner.node_id)
            full = payload.full
            entry.update(accepted=ok, args=full.args, results=full.results,
                         hashes=full.hashes, miner_of=full.miner_of,
                         root=payload.merkle_root)
        self.blocks.append(entry)
        return BlockRecord(t0, time.perf_counter(), ok,
                           {"args": self.args_per_block})

    def release(self) -> None:
        del self.miner, self.verifier, self.pool
        gc.collect()

    # -- the comparison ------------------------------------------------
    def readings(self) -> Dict[str, float]:
        """A sample of ``check_blocks`` of the blocks mined (set-up and
        window), drawn from the seed, each against the reference block of
        the slice submitted for it; the blocks refused, of all; and the
        chips the chunk program was compiled for, which only JAX's record
        of its compilation shows (a miner on fewer chips commits right
        blocks)."""
        k = min(self.traffic["check_blocks"], len(self.blocks))
        sample = sorted(self.rng.choice(len(self.blocks), k, replace=False))
        got = self._compare([self.blocks[i] for i in sample],
                            self.max_steps, self.chips, self.arg_bits)
        got["refused"] = sum(not b["accepted"] for b in self.blocks)
        counts = self.lowered.device_counts(CHUNK_PROGRAM)
        got["miner_chips_short"] = max(0, self.chips - min(counts,
                                                           default=0))
        return got

    def control(self) -> Dict[str, float]:
        """The reference with one stated guarantee broken, in the
        program's place: the first block's results capped at 64 steps,
        not ``max_steps``."""
        base = int(self.slices[0])
        res, hashes, root = ref.full(self.arg_bits, 64, base)
        args = np.arange(self.args_per_block, dtype=np.uint32)
        low = {"accepted": True, "base": base, "args": args,
               "results": res, "hashes": hashes,
               "miner_of": args % self.chips, "root": root}
        return self._compare([low], self.max_steps, self.chips,
                             self.arg_bits)

    @staticmethod
    def _compare(blocks: List[Dict], max_steps: int, chips: int,
                 arg_bits: int) -> Dict[str, float]:
        """Counts: of the blocks mined, the args whose committed result,
        submission hash or miner (``arg % chips``) differs from the
        reference block of the block's slice (every arg, where the
        block's arrays have another shape) and the roots that differ;
        and the blocks refused (by the miner's self-check or the
        verifier)."""
        args = np.arange(1 << arg_bits, dtype=np.uint32)
        wrong = roots = 0
        for b in blocks:
            if "root" not in b:
                continue
            res, hashes, root = ref.full(arg_bits, max_steps, b["base"])
            if (b["args"].shape != args.shape or b["miner_of"].shape
                    != args.shape or b["results"].shape != res.shape
                    or b["hashes"].shape != hashes.shape):
                wrong += len(args)
            else:
                wrong += int(((b["args"] != args)
                              | (b["miner_of"] != args % chips)
                              | (b["results"] != res).any(axis=1)
                              | (b["hashes"] != hashes).any(axis=1)).sum())
            roots += b["root"] != root
        return {"result_mismatch": wrong, "root_mismatch": roots,
                "refused": sum(not b["accepted"] for b in blocks)}
