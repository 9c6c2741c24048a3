"""Substrate tests: data pipeline determinism, optimizer, schedule,
sharding rules, attention banded/masked equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property-based tests need hypothesis")
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.configs.base import InputShape
from repro.data.pipeline import SyntheticTokenPipeline
from repro.launch.mesh import make_mesh
from repro.models.attention import chunked_attention
from repro.optim.adamw import adamw_init, adamw_update
from repro.optim.schedule import cosine_schedule
from repro.sharding.partition import (batch_specs, cache_specs, constrain,
                                      param_specs)


class TestPipeline:
    def test_deterministic_across_instances(self):
        cfg = reduced(get_config("qwen3-0.6b"))
        shape = InputShape("t", 64, 4, "train")
        a = SyntheticTokenPipeline(cfg, shape, seed=5)
        b = SyntheticTokenPipeline(cfg, shape, seed=5)
        for step in (0, 3, 17):
            np.testing.assert_array_equal(
                np.asarray(a.batch(step)["tokens"]),
                np.asarray(b.batch(step)["tokens"]))
        assert a.checksum() == b.checksum()

    def test_different_seed_different_data(self):
        cfg = reduced(get_config("qwen3-0.6b"))
        shape = InputShape("t", 64, 4, "train")
        a = SyntheticTokenPipeline(cfg, shape, seed=0)
        b = SyntheticTokenPipeline(cfg, shape, seed=1)
        assert not np.array_equal(np.asarray(a.batch(0)["tokens"]),
                                  np.asarray(b.batch(0)["tokens"]))
        assert a.checksum() != b.checksum()

    def test_tokens_in_vocab(self):
        cfg = reduced(get_config("whisper-medium"))
        shape = InputShape("t", 128, 2, "train")
        t = np.asarray(SyntheticTokenPipeline(cfg, shape).batch(0)["tokens"])
        assert t.min() >= 0 and t.max() < cfg.vocab_size


class TestAdamW:
    def test_converges_on_quadratic(self):
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = adamw_init(params)
        target = jnp.asarray([1.0, 2.0])

        @jax.jit
        def step(p, s):
            g = jax.grad(lambda q: jnp.sum((q["w"] - target) ** 2))(p)
            return adamw_update(p, g, s, 0.1, weight_decay=0.0)

        for _ in range(200):
            params, state = step(params, state)
        np.testing.assert_allclose(np.asarray(params["w"]),
                                   np.asarray(target), atol=1e-2)

    def test_grad_clip_bounds_update(self):
        params = {"w": jnp.zeros(3)}
        state = adamw_init(params)
        huge = {"w": jnp.full(3, 1e9)}
        p2, _ = adamw_update(params, huge, state, lr=1.0, grad_clip=1.0,
                             weight_decay=0.0)
        assert np.all(np.abs(np.asarray(p2["w"])) < 10.0)

    def test_step_counter(self):
        params = {"w": jnp.zeros(2)}
        state = adamw_init(params)
        _, s1 = adamw_update(params, params, state, 0.1)
        assert int(s1.step) == 1


class TestSchedule:
    @given(st.integers(0, 10_000))
    @settings(max_examples=50, deadline=None)
    def test_bounded_and_positive(self, step):
        lr = float(cosine_schedule(step, peak_lr=1e-3, warmup_steps=100,
                                   total_steps=10_000))
        assert 0.0 <= lr <= 1e-3 * (1 + 1e-6)   # f32 repr of peak_lr

    def test_warmup_then_decay(self):
        lrs = [float(cosine_schedule(s, peak_lr=1.0, warmup_steps=10,
                                     total_steps=100)) for s in range(100)]
        assert lrs[5] < lrs[9]                    # warming up
        assert lrs[99] < lrs[20]                  # decayed


class TestShardingRules:
    def _mesh(self):
        return make_mesh((1, 1), ("data", "model"))

    def test_param_specs_cover_big_matrices(self):
        cfg = reduced(get_config("olmoe-1b-7b"))
        from repro.models.model import build_model
        params = jax.eval_shape(
            lambda: build_model(cfg).init(jax.random.key(0)))
        mesh = self._mesh()
        specs = param_specs(params, mesh)
        flat, _ = jax.tree_util.tree_flatten_with_path(specs)
        # every spec has rank <= its param rank
        pflat, _ = jax.tree_util.tree_flatten_with_path(params)
        for (pa, sp), (pb, pv) in zip(flat, pflat):
            assert len(sp) <= len(pv.shape)

    def test_divisibility_fallback_replicates(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        # mesh size 1 divides everything; use a fake 16-way check instead
        from repro.sharding.partition import _spec_for
        big = make_mesh((1, 1), ("data", "model"))
        spec = _spec_for("whisper/pos_table", (1500, 64), big, True)
        assert isinstance(spec, P)

    def test_constrain_noop_outside_mesh(self):
        x = jnp.ones((4, 4))
        y = constrain(x, "batch", "tensor")
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_batch_specs_shard_batch_dim(self):
        mesh = self._mesh()
        batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        specs = batch_specs(batch, mesh, 8)
        assert specs["tokens"] == P(("data",))

    def test_cache_specs_never_shard_ring_dim(self):
        cfg = reduced(get_config("qwen3-0.6b"))
        from repro.models.model import build_model
        model = build_model(cfg)
        cache = jax.eval_shape(lambda: model.init_cache(8, 64))
        mesh = self._mesh()
        specs = cache_specs(cache, mesh, 8)
        flat, _ = jax.tree_util.tree_flatten_with_path(specs)
        for path, sp in flat:
            key = "/".join(str(getattr(k, "key", k)) for k in path)
            if key.endswith("slot_pos"):
                assert sp == P(*([None] * len(sp))) or sp == P()


class TestBandedAttention:
    @pytest.mark.parametrize("S,T,window", [(64, 64, 16), (128, 128, 32)])
    def test_banded_equals_masked(self, S, T, window):
        """The banded (dynamic-slice) path == the full masked path."""
        B, H, Kv, hd = 1, 2, 2, 8
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.normal(size=(B, S, H, hd)).astype(np.float32))
        k = jnp.asarray(rs.normal(size=(B, T, Kv, hd)).astype(np.float32))
        v = jnp.asarray(rs.normal(size=(B, T, Kv, hd)).astype(np.float32))
        # banded triggers when T > window + chunk
        banded = chunked_attention(q, k, v, causal=True, window=window,
                                   chunk=16)
        masked = chunked_attention(q, k, v, causal=True, window=window,
                                   chunk=S)     # chunk == S -> masked path
        np.testing.assert_allclose(np.asarray(banded), np.asarray(masked),
                                   rtol=1e-4, atol=1e-5)

    def test_window_limits_context(self):
        """A token outside the window must not influence the output."""
        B, S, H, hd = 1, 32, 1, 4
        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.normal(size=(B, S, H, hd)).astype(np.float32))
        k = jnp.asarray(rs.normal(size=(B, S, H, hd)).astype(np.float32))
        v = jnp.asarray(rs.normal(size=(B, S, H, hd)).astype(np.float32))
        out1 = chunked_attention(q, k, v, causal=True, window=4, chunk=8)
        k2 = k.at[:, 0].set(99.0)               # outside window of t>=4
        v2 = v.at[:, 0].set(99.0)
        out2 = chunked_attention(q, k2, v2, causal=True, window=4, chunk=8)
        np.testing.assert_allclose(np.asarray(out1[:, 8:]),
                                   np.asarray(out2[:, 8:]), rtol=1e-5)
