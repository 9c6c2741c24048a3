"""Differential parity harness for ``ModelTrainingWorkload`` — real-model
PoUW (ROADMAP "chain-train the transformer zoo").

Pins the digest contract (canonical little-endian dtype+shape-framed
bytes of gathered arrays, shared between ``PoUWTrainer`` and the chain
workload), mesh-vs-single-device bit-identity, miner-vs-verifier replay
parity, reorg rollback snapshot-policy invariance (mirroring the GAN
tests), forged-evidence rejection, journal round-trip +
``Node.recover`` byte-identity, sim convergence with the new family,
and the ISSUE acceptance loop on ``pnpcoin-demo`` (≥4 blocks, 2-node
convergence, crash recovery, mid-chain reorg).
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import struct
import subprocess
import sys
import textwrap
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.chain import Node
from repro.chain.store import ChainStore, encode_payload, decode_payload
from repro.chain.workloads import ModelTrainingWorkload, default_suite
from repro.chain.workloads.model_train import MICRO_KWARGS
from repro.configs import get_config
from repro.core.pow_train import _light_state_digest
from repro.train.steps import (TrainState, make_train_state, params_digest,
                               tree_digest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def micro_wl(**overrides) -> ModelTrainingWorkload:
    kw = dict(MICRO_KWARGS)
    kw.update(overrides)
    return ModelTrainingWorkload(**kw)


def mt_node(i: int, **node_kwargs) -> Node:
    mesh = node_kwargs.pop("mesh", None)
    return Node(node_id=i, classic_arg_bits=5,
                workloads={"model_train": micro_wl(mesh=mesh)},
                **node_kwargs)


# ---------------------------------------------------------------------------
# the digest contract (satellite: _light_state_digest fragility fix)
# ---------------------------------------------------------------------------


class TestDigestCanonicalization:
    # computed once from the canonical framing; any platform, numpy, or
    # framing drift that changes committed state digests fails here
    PINNED = ("95a659025128acdb00f4e8d98f2542a0"
              "1b5d96804feb77f33a639dce11c383f8")

    @staticmethod
    def _tree():
        return {"a": np.arange(6, dtype="<f4").reshape(2, 3),
                "b": {"w1": np.float64(1.5), "n": np.int32(-7)},
                "c": np.array([True, False])}

    def test_cross_platform_pinned_vector(self):
        assert tree_digest(self._tree()) == self.PINNED

    def test_layout_and_endianness_invariance(self):
        """Fortran-order buffers and big-endian dtypes canonicalize to
        the same bytes — the digest sees values, never memory layout."""
        t = self._tree()
        f = dict(t, a=np.asfortranarray(t["a"]))
        assert tree_digest(f) == self.PINNED
        be = dict(t, a=t["a"].astype(">f4"))
        assert tree_digest(be) == self.PINNED

    def test_dtype_and_shape_framing(self):
        """Same raw bytes under a different dtype or shape must digest
        differently (the old projection digest collided here)."""
        x = np.arange(4, dtype="<f4")
        assert tree_digest({"x": x}) != \
            tree_digest({"x": x.view("<u4")})
        assert tree_digest({"x": x}) != \
            tree_digest({"x": x.reshape(2, 2)})

    def test_path_framing(self):
        assert tree_digest({"a": np.float32(1)}) != \
            tree_digest({"b": np.float32(1)})

    def test_full_params_not_a_projection(self):
        """The digest covers every element — mutating one weight far
        past the old 64-element projection window changes it."""
        x = np.zeros(1024, np.float32)
        base = tree_digest({"w": x})
        y = x.copy()
        y[1000] = 1e-3
        assert tree_digest({"w": y}) != base
        # the old digest summed leaves: a permutation that preserves the
        # sum (and the leading window) must still be detected
        z = x.copy()
        z[100], z[101] = 2.0, -2.0
        zp = x.copy()
        zp[100], zp[101] = -2.0, 2.0
        assert tree_digest({"w": z}) != tree_digest({"w": zp})

    def test_shared_helper_between_trainer_and_workload(self):
        """``PoUWTrainer``'s per-block digest is the same
        ``params_digest`` the chain workload commits."""
        cfg = micro_wl().cfg
        state = make_train_state(cfg, jax.random.key(0))
        trainer_digest = _light_state_digest(state)
        assert trainer_digest == params_digest(state)
        assert trainer_digest == params_digest(state.params)
        assert trainer_digest == tree_digest(state.params)

    def test_jax_and_numpy_trees_agree(self):
        state = make_train_state(micro_wl().cfg, jax.random.key(1))
        host = jax.tree.map(np.asarray, state.params)
        assert params_digest(host) == params_digest(state.params)


def _bf16_jax():
    x = jnp.asarray([[1.0, -2.0, 0.5], [3.0, 0.0, -0.25]], jnp.bfloat16)
    bits = struct.pack("<6H", 0x3F80, 0xC000, 0x3F00, 0x4040, 0x0000,
                       0xBE80)
    return {"w": x}, [("w", "<V2", (2, 3), bits)]


def _f32_numpy():
    return ({"x": np.arange(4, dtype="<f4")},
            [("x", "<f4", (4,), struct.pack("<4f", 0, 1, 2, 3))])


def _scalar():
    # a 0-d leaf is framed as shape (1,), as np.ascontiguousarray has
    # always made it
    return ({"s": np.float64(1.5)},
            [("s", "<f8", (1,), struct.pack("<d", 1.5))])


def _big_endian():
    return ({"b": np.array([1, -2, 3], ">i4")},
            [("b", "<i4", (3,), struct.pack("<3i", 1, -2, 3))])


def _transposed():
    return ({"t": np.arange(6, dtype="<i2").reshape(2, 3).T},
            [("t", "<i2", (3, 2), struct.pack("<6h", 0, 3, 1, 4, 2, 5))])


def _mixed():
    tree = {"z": jnp.float32(2.0),
            "a": [np.array([True, False]), np.int8(-1)],
            "m": {"k": np.array([7], ">u2")}}
    return tree, [("a/0", "|b1", (2,), b"\x01\x00"),
                  ("a/1", "|i1", (1,), b"\xff"),
                  ("m/k", "<u2", (1,), b"\x07\x00"),
                  ("z", "<f4", (1,), struct.pack("<f", 2.0))]


def _written_out(leaves) -> str:
    """sha256 of the framing written out by hand, leaf by leaf:
    ``path | dtype | ndim | shape | little-endian C-order data``."""
    h = hashlib.sha256()
    for path, dtype, shape, data in leaves:
        h.update(path.encode() + b"\x00" + dtype.encode() + b"\x00")
        h.update(struct.pack("<q", len(shape)))
        h.update(struct.pack(f"<{len(shape)}q", *shape))
        h.update(data)
    return h.hexdigest()


class TestDigestStream:
    @pytest.mark.parametrize("case", [_bf16_jax, _f32_numpy, _scalar,
                                      _big_endian, _transposed, _mixed],
                             ids=lambda f: f.__name__.lstrip("_"))
    def test_digest_is_the_written_out_framing(self, case):
        tree, leaves = case()
        assert tree_digest(tree) == _written_out(leaves)

    def test_host_leaf_is_hashed_in_place(self):
        """Hashing a 64 MB host leaf allocates no copy of its bytes."""
        x = np.ones(16 << 20, np.float32)
        tree_digest({"w": x[:16]})             # imports and first spans
        tracemalloc.start()
        try:
            tree_digest({"w": x})
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert peak < 4 << 20, peak


class TestShardingInvariance:
    _SCRIPT = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.chain.workloads.model_train import MICRO_CONFIG
        from repro.launch.mesh import make_mesh
        from repro.sharding.partition import param_shardings
        from repro.train.steps import make_train_state, params_digest, \\
            tree_digest

        mesh = make_mesh((2, 4), ("data", "model"))
        x = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
        host = {"w": x}
        for spec in [P("data", "model"), P("model", None), P()]:
            sharded = {"w": jax.device_put(x, NamedSharding(mesh, spec))}
            assert tree_digest(sharded) == tree_digest(host), spec
        # a real param tree through the partition rules
        state = make_train_state(MICRO_CONFIG, jax.random.key(0))
        sharded = jax.device_put(
            state.params, param_shardings(state.params, mesh))
        assert params_digest(sharded) == params_digest(state.params)
        print("DIGEST_OK")
    """)

    def test_digest_is_sharding_invariant_8_devices(self):
        """gather-then-hash: the digest of an array sharded across an
        8-device host mesh equals the digest of its host copy, for any
        partition spec (subprocess so the XLA device-count flag doesn't
        leak)."""
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        out = subprocess.run([sys.executable, "-c", self._SCRIPT], env=env,
                             capture_output=True, text=True, timeout=600,
                             cwd=REPO)
        assert out.returncode == 0, out.stderr[-3000:]
        assert "DIGEST_OK" in out.stdout


# ---------------------------------------------------------------------------
# mesh-vs-single-device parity
# ---------------------------------------------------------------------------


class TestMeshParity:
    def test_mesh_and_plain_nodes_interverify_bit_identically(self):
        """A node training under a device mesh (sharded state + batch
        placement + activation rules) and a plain single-device node
        must commit bit-identical blocks — each accepts the other's
        work by replaying on its own setup."""
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        a = mt_node(0, mesh=mesh)
        b = mt_node(1)
        ra = a.mine_block("model_train")
        assert b.receive(ra.record.to_block(), ra.payload, origin=0)
        rb = b.mine_block("model_train")
        assert a.receive(rb.record.to_block(), rb.payload, origin=1)
        assert a.workloads["model_train"].state_digest() == \
            b.workloads["model_train"].state_digest()
        assert [blk.block_hash for blk in a.ledger.blocks] == \
            [blk.block_hash for blk in b.ledger.blocks]


# ---------------------------------------------------------------------------
# miner-vs-verifier replay parity
# ---------------------------------------------------------------------------


class TestReplayParity:
    def test_two_nodes_converge_bit_identically(self):
        a, b = mt_node(0), mt_node(1)
        receipts = [a.mine_block("model_train") for _ in range(3)]
        for r in receipts:
            assert b.receive(r.record.to_block(), r.payload, origin=0)
        wa, wb = a.workloads["model_train"], b.workloads["model_train"]
        assert wa.round == wb.round == 3
        assert wa.state_digest() == wb.state_digest()
        assert a.book.balances == b.book.balances
        assert [blk.block_hash for blk in a.ledger.blocks] == \
            [blk.block_hash for blk in b.ledger.blocks]
        # and a third, late-joining node adopts the whole chain by replay
        c = mt_node(2)
        assert c.consider_chain(list(a.ledger.blocks), a.chain_payloads())
        assert c.workloads["model_train"].state_digest() == \
            wa.state_digest()

    def test_every_block_advances_the_state(self):
        """The chain does useful work: each block is real SGD, so every
        block commits a new params digest, a higher train height, and a
        finite loss (the synthetic token stream is near-uniform, so the
        loss itself hovers at the data entropy — progress is pinned by
        the state chain, not by loss descent)."""
        a = mt_node(0)
        seen = set()
        for r in range(5):
            p = a.mine_block("model_train").payload
            assert p.train_height == r
            assert np.isfinite(p.loss)
            assert p.state_digest not in seen
            seen.add(p.state_digest)


# ---------------------------------------------------------------------------
# forged evidence rejection
# ---------------------------------------------------------------------------


class TestForgedEvidenceRejection:
    def _honest_payload(self):
        a = mt_node(0)
        return a.mine_block("model_train").payload

    def _assert_rejected(self, payload):
        v = mt_node(9).workloads["model_train"]
        assert not v.verify(payload)
        assert v.round == 0 and v.is_pristine()

    def test_honest_accepted(self):
        p = self._honest_payload()
        v = mt_node(9).workloads["model_train"]
        assert v.verify(p)
        assert v.round == 1

    def test_forged_state_digest(self):
        self._assert_rejected(dataclasses.replace(
            self._honest_payload(), state_digest="00" * 32))

    def test_forged_loss(self):
        self._assert_rejected(dataclasses.replace(
            self._honest_payload(), loss=0.0))

    def test_corrupted_micro_proof(self):
        p = self._honest_payload()
        proof = np.array(p.micro_proof)
        proof[0, 0] ^= 1
        self._assert_rejected(dataclasses.replace(p, micro_proof=proof))

    def test_stripped_micro_proof(self):
        self._assert_rejected(dataclasses.replace(
            self._honest_payload(), micro_proof=None))

    def test_forged_merkle_root(self):
        self._assert_rejected(dataclasses.replace(
            self._honest_payload(), merkle_root="ff" * 32))

    def test_forged_n_miners_reward_grab(self):
        self._assert_rejected(dataclasses.replace(
            self._honest_payload(), n_miners=1))

    def test_future_height_unverifiable(self):
        b = mt_node(1)
        b.mine_block("model_train")
        r2 = b.mine_block("model_train")
        self._assert_rejected(r2.payload)

    def test_corrupted_params_chain_rejected_by_peer(self):
        """A miner whose *state* is corrupted commits digests no honest
        peer can reproduce — the block is rejected on receive."""
        a, b = mt_node(0), mt_node(1)
        wa = a.workloads["model_train"]
        wa._ensure_state()
        bad = jax.tree.map(lambda x: x + 1e-3, wa._state.params)
        wa._state = TrainState(params=bad, opt=wa._state.opt)
        r = a.mine_block("model_train")
        assert not b.receive(r.record.to_block(), r.payload, origin=0)
        assert b.workloads["model_train"].is_pristine()


# ---------------------------------------------------------------------------
# reorg rollback (mirrors TestGanRollback)
# ---------------------------------------------------------------------------


class TestModelTrainRollback:
    @pytest.mark.parametrize("snapshot_interval", [0, 2])
    def test_reorg_rolls_trainer_back(self, snapshot_interval):
        """A reorg that drops local model-train blocks must rewind the
        train state so the node can re-mine them on the adopted chain —
        and the outcome is invariant to the fork-choice snapshot policy
        (genesis replay == ringed checkpoints)."""
        a = mt_node(0, snapshot_interval=snapshot_interval)
        b = mt_node(1)
        a.mine_block("model_train")
        b_payload = b.mine_block("model_train").payload  # identical step 0
        assert a.workloads["model_train"].state_digest() == \
            b.workloads["model_train"].state_digest()
        a.mine_block("model_train")                      # A: steps 0, 1
        for _ in range(3):                               # B: step 0 + classic
            b.mine_block("classic")
        assert a.workloads["model_train"].round == 2
        assert a.consider_chain(list(b.ledger.blocks), b.chain_payloads())
        # step 1 was reorged away -> train state rewound to step 1's start
        assert a.workloads["model_train"].round == 1
        assert a.workloads["model_train"].state_digest() == \
            b.workloads["model_train"].state_digest()
        # and the chain keeps extending consistently: A re-mines step 1,
        # B accepts it on receive (bit-identical replay)
        receipt = a.mine_block("model_train")
        assert b.receive(receipt.record.to_block(), receipt.payload,
                         origin=0)
        assert b_payload.train_height == 0               # sanity

    def test_failed_candidate_leaves_state_untouched(self):
        a, b = mt_node(0), mt_node(1)
        a.mine_block("model_train")
        digest = a.workloads["model_train"].state_digest()
        b.mine_block("model_train")
        b.mine_block("model_train")
        blocks = list(b.ledger.blocks)
        payloads = b.chain_payloads()
        corrupted = [payloads[0],
                     dataclasses.replace(payloads[1], state_digest="00" * 32)]
        assert not a.consider_chain(blocks, corrupted)
        assert a.workloads["model_train"].round == 1
        assert a.workloads["model_train"].state_digest() == digest


# ---------------------------------------------------------------------------
# journal round-trip + Node.recover
# ---------------------------------------------------------------------------


class TestJournalRecovery:
    def test_payload_roundtrip_byte_identity(self):
        a = mt_node(0)
        for _ in range(2):
            p = a.mine_block("model_train").payload
            enc = encode_payload(p)
            dec = decode_payload(enc)
            assert encode_payload(dec) == enc
            np.testing.assert_array_equal(dec.micro_proof, p.micro_proof)
            assert dec.state_digest == p.state_digest
            assert dec.loss == p.loss

    def test_node_recover_replays_model_train_chain(self):
        store = ChainStore()
        a = Node(node_id=0, classic_arg_bits=5,
                 workloads={"model_train": micro_wl()}, store=store)
        for _ in range(3):
            a.mine_block("model_train")
        a.mine_block("classic")
        # crash: rebuild from the journal into a fresh shell with a
        # fresh workload instance (consensus params, not state, are
        # what survives a crash)
        shell = mt_node(0)
        rec = Node.recover(store, node=shell)
        assert rec.last_recovery.adopted_height == 4
        assert rec.ledger.height == a.ledger.height
        assert [blk.block_hash for blk in rec.ledger.blocks] == \
            [blk.block_hash for blk in a.ledger.blocks]
        assert rec.book.balances == a.book.balances
        # byte-identity: the replayed chain re-encodes to the same bytes
        for p0, p1 in zip(a.chain_payloads(), rec.chain_payloads()):
            assert encode_payload(p0) == encode_payload(p1)
        assert rec.workloads["model_train"].state_digest() == \
            a.workloads["model_train"].state_digest()
        # and the recovered node keeps mining blocks peers accept
        r = rec.mine_block("model_train")
        assert a.receive(r.record.to_block(), r.payload, origin=0)


# ---------------------------------------------------------------------------
# sim convergence with the new family
# ---------------------------------------------------------------------------


class TestSimConvergence:
    def test_heterogeneous_scenario_includes_model_train(self):
        from repro.chain.sim import heterogeneous_scenario
        sim = heterogeneous_scenario(seed=3)
        rep = sim.run()
        assert rep.converged
        assert rep.credit_divergence == 0.0
        honest = sim.honest_nodes
        mined = sum(p is not None and p.workload == "model_train"
                    for p in honest[0].chain_payloads())
        assert mined >= 2
        digests = {n.workloads["model_train"].state_digest()
                   for n in honest}
        assert len(digests) == 1

    def test_default_suite_grows_the_family(self):
        suite = default_suite(seed=5, model_train=dict(MICRO_KWARGS))
        assert isinstance(suite["model_train"], ModelTrainingWorkload)
        assert suite["model_train"].name == "model_train"
        assert suite["model_train"].is_pristine()


# ---------------------------------------------------------------------------
# ISSUE acceptance: pnpcoin-demo end to end
# ---------------------------------------------------------------------------


class TestPnpcoinDemoAcceptance:
    @staticmethod
    def _node(i: int, **kw) -> Node:
        wl = ModelTrainingWorkload(cfg=get_config("pnpcoin-demo"),
                                   seq_len=16, batch=2,
                                   block_microsteps=1, n_miners=2)
        return Node(node_id=i, classic_arg_bits=5,
                    workloads={"model_train": wl}, **kw)

    @pytest.mark.slow
    def test_two_node_chain_with_recovery_and_reorg(self):
        """≥4 model-train blocks on the real ``pnpcoin-demo``
        transformer across two nodes, verified by microbatch
        re-execution, converging bit-identically — then pinned through
        a crash/``Node.recover`` cycle and a mid-chain reorg."""
        store = ChainStore()
        a = self._node(0, store=store)
        b = self._node(1)
        for _ in range(4):
            r = a.mine_block("model_train")
            assert b.receive(r.record.to_block(), r.payload, origin=0)
        assert a.workloads["model_train"].state_digest() == \
            b.workloads["model_train"].state_digest()
        assert a.book.balances == b.book.balances
        # crash/recover cycle: byte-identical chain from the journal
        rec = Node.recover(store, node=self._node(0))
        assert rec.ledger.height == 4
        assert [blk.block_hash for blk in rec.ledger.blocks] == \
            [blk.block_hash for blk in a.ledger.blocks]
        assert rec.workloads["model_train"].state_digest() == \
            a.workloads["model_train"].state_digest()
        # mid-chain reorg: the recovered node mines a private block while
        # b's chain grows longer; fork choice rolls the train state back
        rec.mine_block("model_train")                  # rec: height 5
        r5 = b.mine_block("model_train")
        b.mine_block("classic")                        # b: height 6
        assert rec.consider_chain(list(b.ledger.blocks),
                                  b.chain_payloads())
        assert rec.workloads["model_train"].round == 5
        assert rec.workloads["model_train"].state_digest() == \
            b.workloads["model_train"].state_digest()
        assert r5.payload.train_height == 4            # sanity
