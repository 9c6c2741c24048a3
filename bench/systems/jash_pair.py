"""Driver of the classic cell: a miner ``Node`` and a verifier ``Node``,
each in its own trust domain (no shared ``VerifyCache``), in one process
on one chip.

Every block of the window is mined with the default policy on an empty
researcher queue (``mine_block(None)``: the section 3.4 classic
fallback) and handed to the verifier (``receive``), which re-verifies it
itself.  The protocol fixes these blocks' inputs (the classic jash
hashes a fixed salt, not the chain tip), so the seed changes nothing
here."""
from __future__ import annotations

import gc
import time
from typing import Dict, List

from bench.harness import BlockRecord, annotate
from bench.lowerings import watch
from bench.reference import jash as ref

from repro.chain import Node
from repro.chain.workload import ChainError

# the executor's single-device optimal-mode reducer, as JAX names it when
# it lowers the program (``jit_reduce_all`` in the trace)
SEARCH_PROGRAM = "jit(reduce_all)"


class System:
    def __init__(self, cell, seed: int) -> None:
        self.cfg = cell.config
        self.arg_bits = self.cfg["classic_arg_bits"]
        self.args_per_block = 1 << self.arg_bits
        self.trace_devices = [0]
        self.capture_s = 0.0
        self.blocks: List[Dict] = []
        self.lowerings = watch()               # before anything is lowered

    def setup(self) -> None:
        self.miner, self.verifier = (
            Node(node_id=self.cfg[role]["node_id"],
                 classic_arg_bits=self.arg_bits,
                 block_reward=self.cfg["block_reward"])
            for role in ("miner", "verifier"))
        self.block()                           # compiles every program

    def block(self) -> BlockRecord:
        t0 = time.perf_counter()
        ok = False
        with annotate("bench.mine_block"):
            try:
                r = self.miner.mine_block(None)
            except ChainError:                 # failed its self-check
                r = None
        if r is None:
            self.blocks.append({"accepted": False})
        else:
            with annotate("bench.receive"):
                ok = self.verifier.receive(r.record.to_block(), r.payload,
                                           origin=self.miner.node_id)
            self.blocks.append({"accepted": ok, "arg": r.payload.best_arg,
                                "res": r.payload.best_res,
                                "root": r.payload.merkle_root})
        return BlockRecord(t0, time.perf_counter(), ok,
                           {"hashes": self.args_per_block})

    def release(self) -> None:
        del self.miner, self.verifier
        gc.collect()

    # -- the comparison ------------------------------------------------
    def readings(self) -> Dict[str, float]:
        """Every block mined (set-up and window), against the reference
        search over the whole nonce space; and the nonces the search
        program was not handed.  Every block searches the same space and
        finds the same winner (the protocol fixes the input), so a search
        over a part that holds the winner commits the right block: only
        the extent of the program's nonce argument, as JAX lowered it for
        the miner and the verifier, shows the part left out."""
        got = self._compare(self.blocks, ref.classic(self.arg_bits))
        sizes = self.lowerings.arg_sizes(SEARCH_PROGRAM)
        got["search_extent_short"] = max(
            0, self.args_per_block - min(sizes, default=0))
        return got

    def control(self) -> Dict[str, float]:
        """The reference with one stated guarantee broken, in the
        program's place: a single SHA-256 instead of the double hash."""
        arg, words, root = ref.classic(self.arg_bits, rounds=1)
        low = {"accepted": True, "arg": arg, "res": words.tobytes().hex(),
               "root": root}
        return self._compare([low], ref.classic(self.arg_bits))

    @staticmethod
    def _compare(blocks: List[Dict], want) -> Dict[str, float]:
        """Counts of blocks: refused (by the miner's self-check or the
        verifier), and, of the blocks mined, each answer that differs."""
        arg, words, root = want
        mined = [b for b in blocks if "root" in b]
        return {
            "answer_mismatch": sum(b["arg"] != arg
                                   or b["res"] != words.tobytes().hex()
                                   for b in mined),
            "root_mismatch": sum(b["root"] != root for b in mined),
            "refused": sum(not b["accepted"] for b in blocks)}
