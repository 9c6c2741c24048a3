"""Compile the main-path kernels for a TPU v5e chip that is described,
not attached: what the chip's compiler refuses fails here, at no chip
time.  Nothing runs, so nothing here says anything about results or
speed.

The topology is described inside a module-scoped fixture and never at
import: only one process may load the TPU library at a time, and the
suite's workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("nb", [1, 2])
def test_sha256_pallas_compiles(one_chip, nb):
    from repro.kernels.sha256 import TILE_N, sha256_pallas
    c = _compile(lambda p: sha256_pallas(p, interpret=False),
                 ((4 * TILE_N, 16 * nb), jnp.uint32), sharding=one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_pallas_compiles_bf16_head_dim_128(one_chip):
    from repro.kernels.flash_attention import flash_attention_pallas
    qkv = ((16, 1024, 128), jnp.bfloat16)
    c = _compile(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, interpret=False), qkv, qkv, qkv,
        sharding=one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_decay_scan_pallas_compiles_at_rg_lru_width(one_chip):
    from repro.configs import get_config
    from repro.kernels.decay_scan import decay_scan_pallas
    C = get_config("recurrentgemma-2b").lru_width            # 2560
    ab = ((1, 2048, C), jnp.float32)
    c = _compile(lambda a, b, h: decay_scan_pallas(a, b, h, interpret=False),
                 ab, ab, ((1, C), jnp.float32), sharding=one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_classic_full_mode_chunk_compiles_at_default_chunk(one_chip):
    from repro.core.authority import classic_jash
    from repro.core.executor import DEFAULT_CHUNK, _chunk_executor
    step = _chunk_executor(classic_jash().fn, None, (), 1)
    args = jax.ShapeDtypeStruct((DEFAULT_CHUNK,), jnp.uint32,
                                sharding=one_chip)
    c = step.lower(args).compile()
    res, hashes, leaves = c.out_info
    assert res.shape == (DEFAULT_CHUNK, 8)
    assert hashes.shape == leaves.shape == (DEFAULT_CHUNK, 8)
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30
