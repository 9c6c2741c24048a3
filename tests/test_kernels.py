"""Per-kernel validation: Pallas in interpret mode (asked for by name,
``interpret=True``) vs pure-jnp oracles, swept over shapes and dtypes;
SHA-256 additionally vs hashlib."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


class TestSha256:
    @pytest.mark.parametrize("n,w", [(1, 1), (7, 4), (128, 12), (200, 13),
                                     (64, 14), (16, 20), (3, 32)])
    def test_vs_hashlib(self, n, w):
        msg = np.random.RandomState(n * 31 + w).randint(
            0, 2**32, (n, w), dtype=np.uint32)
        gt = ref.sha256_words_hashlib(msg)
        got_jnp = np.asarray(ops.sha256_words(jnp.asarray(msg),
                                              backend="jnp"))
        got_pl = np.asarray(ops.sha256_words(jnp.asarray(msg),
                                             backend="pallas",
                                             interpret=True))
        np.testing.assert_array_equal(got_jnp, gt)
        np.testing.assert_array_equal(got_pl, gt)

    def test_empty_words_vector(self):
        # known vector: sha256 of 4 zero bytes
        import hashlib
        msg = np.zeros((1, 1), np.uint32)
        want = np.frombuffer(hashlib.sha256(b"\x00" * 4).digest(), ">u4")
        got = np.asarray(ops.sha256_words(jnp.asarray(msg)))
        np.testing.assert_array_equal(got[0], want.astype(np.uint32))

    def test_deterministic_across_jit(self):
        msg = jnp.arange(24, dtype=jnp.uint32).reshape(2, 12)
        a = ops.sha256_words(msg)
        b = jax.jit(lambda m: ops.sha256_words(m))(msg)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestDecayScan:
    @pytest.mark.parametrize("shape", [(1, 4, 8), (2, 37, 130), (3, 64, 256),
                                       (1, 128, 129)])
    @pytest.mark.parametrize("dtype", [np.float32])
    def test_vs_ref(self, shape, dtype):
        B, S, C = shape
        rs = np.random.RandomState(sum(shape))
        a = jnp.asarray(rs.uniform(0.3, 1.0, shape).astype(dtype))
        b = jnp.asarray(rs.normal(size=shape).astype(dtype))
        h0 = jnp.asarray(rs.normal(size=(B, C)).astype(dtype))
        got, gotT = ops.decay_scan(a, b, h0, backend="pallas", seq_chunk=16,
                                     interpret=True)
        want = ref.decay_scan_ref(a, b, h0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gotT),
                                   np.asarray(want[:, -1]),
                                   rtol=1e-5, atol=1e-5)

    def test_matches_sequential_python(self):
        B, S, C = 1, 9, 3
        rs = np.random.RandomState(0)
        a = rs.uniform(0.1, 0.9, (B, S, C)).astype(np.float32)
        b = rs.normal(size=(B, S, C)).astype(np.float32)
        h = np.zeros((B, C), np.float32)
        outs = []
        for t in range(S):
            h = a[:, t] * h + b[:, t]
            outs.append(h.copy())
        want = np.stack(outs, axis=1)
        got = ref.decay_scan_ref(jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-6)

    def test_chunk_carry_equivalence(self):
        """Chunked execution with h0 carry == single call (ops contract)."""
        B, S, C = 2, 32, 16
        rs = np.random.RandomState(3)
        a = jnp.asarray(rs.uniform(0.3, 1.0, (B, S, C)).astype(np.float32))
        b = jnp.asarray(rs.normal(size=(B, S, C)).astype(np.float32))
        full = ref.decay_scan_ref(a, b)
        h1 = ref.decay_scan_ref(a[:, :16], b[:, :16])
        h2 = ref.decay_scan_ref(a[:, 16:], b[:, 16:], h0=h1[:, -1])
        np.testing.assert_allclose(np.asarray(full[:, 16:]),
                                   np.asarray(h2), rtol=1e-5, atol=1e-5)


class TestWkv6:
    @pytest.mark.parametrize("shape", [(1, 5, 1, 4, 4), (2, 19, 3, 8, 8),
                                       (1, 33, 2, 16, 16)])
    def test_vs_ref(self, shape):
        B, S, H, K, V = shape
        rs = np.random.RandomState(sum(shape))
        mk = lambda *s: jnp.asarray(rs.normal(size=s).astype(np.float32))
        r, k = mk(B, S, H, K), mk(B, S, H, K)
        w = jax.nn.sigmoid(mk(B, S, H, K)) * 0.5 + 0.5
        v = mk(B, S, H, V)
        u = mk(H, K)
        s0 = mk(B, H, K, V)
        got_o, got_s = ops.wkv6(r, k, v, w, u, s0, backend="pallas",
                                seq_chunk=7, interpret=True)
        want_o, want_s = ref.wkv6_ref(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                                   rtol=1e-4, atol=1e-4)

    def test_recurrence_semantics(self):
        """One step by hand: o_0 = r (s0 + (u*k) v^T), s_1 = w*s0 + k v^T."""
        B, S, H, K, V = 1, 1, 1, 3, 2
        rs = np.random.RandomState(7)
        r = rs.normal(size=(B, S, H, K)).astype(np.float32)
        k = rs.normal(size=(B, S, H, K)).astype(np.float32)
        v = rs.normal(size=(B, S, H, V)).astype(np.float32)
        w = rs.uniform(0.5, 1.0, (B, S, H, K)).astype(np.float32)
        u = rs.normal(size=(H, K)).astype(np.float32)
        s0 = rs.normal(size=(B, H, K, V)).astype(np.float32)
        o, sT = ref.wkv6_ref(*map(jnp.asarray, (r, k, v, w, u, s0)))
        kv = np.einsum("k,v->kv", k[0, 0, 0], v[0, 0, 0])
        want_o = np.einsum("k,kv->v", r[0, 0, 0],
                           s0[0, 0] + u[0][:, None] * kv)
        want_s = w[0, 0, 0][:, None] * s0[0, 0] + kv
        np.testing.assert_allclose(np.asarray(o)[0, 0, 0], want_o, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(sT)[0, 0], want_s, rtol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("shape", [(1, 32, 32, 2, 1, 8),
                                       (2, 64, 64, 4, 2, 16),
                                       (1, 48, 48, 3, 3, 8)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_vs_chunked_ref(self, shape, causal):
        from repro.models.attention import chunked_attention
        B, S, T, H, Kv, hd = shape
        rs = np.random.RandomState(sum(shape))
        q = jnp.asarray(rs.normal(size=(B, S, H, hd)).astype(np.float32))
        k = jnp.asarray(rs.normal(size=(B, T, Kv, hd)).astype(np.float32))
        v = jnp.asarray(rs.normal(size=(B, T, Kv, hd)).astype(np.float32))
        got = ops.flash_attention(q, k, v, causal=causal, backend="pallas",
                                  bq=16, bk=16, interpret=True)
        want = chunked_attention(q, k, v, causal=causal, chunk=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_online_softmax_stability(self):
        """Large score magnitudes must not overflow (the online-max)."""
        B, S, H, hd = 1, 32, 1, 8
        q = jnp.full((B, S, H, hd), 30.0)
        k = jnp.full((B, S, H, hd), 30.0)
        v = jnp.ones((B, S, H, hd))
        out = ops.flash_attention(q, k, v, causal=True, backend="pallas",
                                  bq=8, bk=8, interpret=True)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-5)
