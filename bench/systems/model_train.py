"""Driver of the ``model_train`` cells: one journaling ``Node`` mining
``ModelTrainingWorkload`` blocks back to back.

Set-up builds the node, mines the first ``check_blocks`` blocks through
``mine_block`` (the first compiles the block step) and keeps what the
comparison needs of them; the window then mines on the same node.  After
the window the node is dropped and the plain reference
(``bench/reference/transformer.py``) trains the same first blocks from
the seed."""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench.harness import BlockRecord, annotate
from bench.reference import commit
from bench.reference import transformer as ref

from repro.chain import ChainStore, Node
from repro.chain.workload import ChainError
from repro.chain.workloads.model_train import ModelTrainingWorkload
from repro.configs.base import ModelConfig
from repro.train.steps import TrainHparams


def model_config(cfg: dict) -> ModelConfig:
    """The program's config object for a Hugging Face style config."""
    run = cfg["run"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg.get("name", cfg["model_type"]), family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // h,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qk_norm=True, tie_embeddings=cfg["tie_word_embeddings"],
        dtype=run["param_dtype"], opt_dtype=run["optimizer_state_dtype"],
        remat=run["remat"], rope_theta=float(cfg["rope_theta"]),
        citation=cfg["source"])


def hparams(cfg: dict) -> TrainHparams:
    hp = cfg["run"]["hparams"]
    return TrainHparams(peak_lr=hp["peak_lr"], warmup_steps=hp["warmup_steps"],
                        total_steps=hp["total_steps"],
                        weight_decay=hp["weight_decay"],
                        grad_clip=hp["grad_clip"])


class System:
    """``seed`` keys the weights and the token stream (``seed % (2**31 -
    1)``, the workload's own seed)."""

    def __init__(self, cell, seed: int) -> None:
        self.cfg, self.traffic = cell.config, cell.traffic
        self.seed = seed % (2 ** 31 - 1)
        self.micro = self.traffic["block_microsteps"]
        self.blocks_checked = self.traffic["check_blocks"]
        self.loss_blocks = self.traffic["loss_blocks"]
        self.tokens_per_block = (self.micro * self.traffic["batch"]
                                 * self.traffic["seq_len"])
        self.trace_devices = [0]
        self.capture_s = 0.0
        self.payloads: List = []

    # -- the program ---------------------------------------------------
    def _workload(self) -> ModelTrainingWorkload:
        return ModelTrainingWorkload(
            cfg=model_config(self.cfg), seq_len=self.traffic["seq_len"],
            batch=self.traffic["batch"], seed=self.seed,
            block_microsteps=self.micro, hp=hparams(self.cfg),
            n_miners=self.cfg["run"]["n_miners"])

    def setup(self) -> None:
        self.wl = self._workload()
        self.store = ChainStore()
        self.node = Node(node_id=0, workloads={"model_train": self.wl},
                         store=self.store,
                         snapshot_interval=self.cfg["run"]["node"][
                             "snapshot_interval"])
        for k in range(self.blocks_checked):
            self.payloads.append(self.node.mine_block("model_train").payload)
            t0 = time.perf_counter()
            state = self.wl.snapshot()[1]
            if k == 0:
                self.moments = ref.leaf_norms(state.opt.m)
            if k == self.blocks_checked - 1:
                self.params_last = jax.device_get(state.params)
            del state
            self.capture_s += time.perf_counter() - t0

    def block(self) -> BlockRecord:
        t0 = time.perf_counter()
        ok = True
        with annotate("bench.mine_block"):
            try:
                self.payloads.append(
                    self.node.mine_block("model_train").payload)
            except ChainError:
                ok = False
        t1 = time.perf_counter()
        return BlockRecord(t0, t1, ok, {"tokens": self.tokens_per_block})

    def release(self) -> None:
        """Read back the journal, then free every device buffer the
        program holds."""
        read = self.store.read_chain()
        self.journal = ([b.block_hash for b in read.blocks],
                        [p.state_digest for p in read.payloads])
        self.ledger = ([b.block_hash for b in self.node.ledger.blocks],
                       [p.state_digest for p in self.payloads])
        del self.node, self.wl, self.store
        gc.collect()

    # -- the comparison ------------------------------------------------
    def _shape(self) -> ref.Shape:
        return ref.Shape.of(self.cfg, self.traffic)

    def reference(self, matmul_dtype=None, fault=None):
        return ref.run(self._shape(), self.cfg["run"]["hparams"], self.seed,
                       self.micro, self.blocks_checked, matmul_dtype, fault)

    def readings(self) -> Dict[str, float]:
        """Compare the program's first blocks with the reference's, and
        re-derive every commitment the blocks carry."""
        want, p0 = self.reference()
        got = ref.Readings(
            losses=[p.loss for p in self.payloads[:self.blocks_checked]],
            moments=self.moments,
            changes=ref.change_norms(self.params_last, p0))
        del p0
        leaves = list(want.moments)
        self.leaf_gaps = {
            "moment": ref.leaf_gaps(got.moments, want.moments, leaves),
            "update": ref.leaf_gaps(got.changes, want.changes, leaves),
            "losses": [got.losses, want.losses]}
        out = ref.compare(got, want, self.loss_blocks)
        out.update(self._commitments())
        return out

    def control(self) -> Dict[str, float]:
        """The reference in the program's place, its matmuls in float8."""
        want, p0 = self.reference()
        del p0
        low, p0 = self.reference("float8_e4m3fn")
        del p0
        return ref.compare(low, want, self.loss_blocks)

    def witness(self) -> Dict[str, float]:
        """A witness beside the program: the reference with every
        matmul's inputs and gradients in bfloat16, the program's own
        precision, against the float32 reference; every check block's
        loss counts, and each block's loss gap is given apart
        (``loss_gap.<block>``)."""
        want, p0 = self.reference()
        del p0
        low, p0 = self.reference("bfloat16")
        del p0
        out = ref.compare(low, want, self.blocks_checked)
        out.update({f"loss_gap.{h + 1}": abs(a - b) for h, (a, b)
                    in enumerate(zip(low.losses, want.losses))})
        return out

    def faults(self) -> Dict[str, Dict[str, float]]:
        """Each planted fault's readings, the reference in the program's
        place.  A block step that returns its state unchanged reads 1 on
        ``moment_gap`` and ``update_gap`` by construction (no run)."""
        want, p0 = self.reference()
        del p0
        out = {}
        for fault in ref.FAULTS:
            got, p0 = self.reference(fault=fault)
            del p0
            out[fault] = ref.compare(got, want, self.loss_blocks)
        return out

    def _commitments(self) -> Dict[str, float]:
        sh = self._shape()
        batch_bad = 0
        for h, p in enumerate(self.payloads[:self.blocks_checked]):
            for m in range(self.micro):
                b = jax.device_get(ref.batch(sh, self.seed, h, m))
                if bytes.fromhex(commit.tree_digest(b)) != \
                        p.micro_proof[m, :32].tobytes():
                    batch_bad += 1
        root_bad = 0
        for h, p in enumerate(self.payloads):
            proof = np.asarray(p.micro_proof, np.uint8)
            leaves = [np.int64(h).tobytes() + np.int64(m).tobytes()
                      + proof[m].tobytes() for m in range(self.micro)]
            root_bad += commit.merkle_root(leaves) != p.merkle_root
        last = self.payloads[self.blocks_checked - 1]
        digest_bad = int(commit.tree_digest(self.params_last)
                         != last.state_digest)
        hashes, digests = self.ledger
        j_hashes, j_digests = self.journal
        journal_bad = (abs(len(hashes) - len(j_hashes))
                       + sum(a != b for a, b in zip(hashes, j_hashes))
                       + sum(a != b for a, b in zip(digests, j_digests)))
        return {"batch_mismatch": batch_bad, "root_mismatch": root_bad,
                "digest_mismatch": digest_bad,
                "journal_mismatch": journal_bad}
