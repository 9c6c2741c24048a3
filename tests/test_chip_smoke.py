"""``chip_smoke.py`` at tiny sizes on the CPU, through its test-only
``Sizes`` override: every phase of the one-chip run, the paths of the
four-chip run on four virtual devices, and the refusals that keep it
from printing a result where there is no TPU."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

# the one-chip run's phases at CPU scale: same code, same checks
_TINY = textwrap.dedent("""
    from repro.chain.workloads.model_train import MICRO_CONFIG
    TINY = chip_smoke.Sizes(platform="cpu", interpret=True,
                            jash_arg_bits=8, classic_arg_bits=10,
                            n_headers=256, model=MICRO_CONFIG, seq_len=16,
                            batch=4, microsteps=2, n_model_blocks=2)
""")


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    return chip_smoke


def test_one_chip_phases_at_tiny_size(chip_smoke, capsys):
    ns = {"chip_smoke": chip_smoke}
    exec(_TINY, ns)
    dev = chip_smoke.run(ns["TINY"])
    assert dev == {"platform": "cpu", "kind": "cpu", "count": 1}
    out = capsys.readouterr().out
    for phase in ("[device]", "[jash]", "[sha256]", "[model]"):
        assert phase in out
    assert "[jash] ok=True height=4" in out
    assert "eq_jnp=True eq_hashlib=True" in out
    assert "adopted=2" in out and "digest_eq=True" in out


def test_refuses_a_platform_other_than_tpu(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="need 'tpu'"):
        chip_smoke.phase_device(chip_smoke.Sizes(), 1)


@pytest.mark.parametrize("alone", [False, True],
                         ids=["cpu-platform", "script-alone"])
def test_exits_nonzero_without_a_result(tmp_path, alone):
    """Run as a user would: on the CPU, and copied into a directory
    that holds nothing else of the repo.  Both exit non-zero and print
    no result line."""
    script = SCRIPT
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, {repo!r})
    import chip_smoke
    from repro.launch.mesh import make_host_mesh
    {tiny}
    clock = chip_smoke._CompileClock()
    mesh = make_host_mesh()
    assert dict(mesh.shape) == {{"data": 4}}, mesh.shape
    # full + optimal blocks over the miner axis verify on a plain node
    chip_smoke.mesh_jash(TINY, clock, mesh)
    # FSDP training over the 4-device mesh runs, journals, and replays
    # bit-identically on a second node with the same mesh
    cfg = chip_smoke._model_cfg(TINY)
    mined = chip_smoke._mine_model_chain(TINY, clock, cfg, "mesh-model",
                                         mesh=mesh)
    chip_smoke._replay(TINY, clock, cfg, mined, "mesh-model", mesh=mesh)
    print("MESH_OK")
""")


def test_four_device_mesh_miners_against_plain_and_replay():
    """The four-chip phase's paths on four virtual CPU devices
    (subprocess, so the device-count flag stays out of this process).
    Auto mesh axes are what let the model's sharding constraints run
    on a mesh of more than one device."""
    code = _MESH_SCRIPT.format(repo=REPO, tiny=_TINY)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    assert "MESH_OK" in out.stdout
    assert "plain_accepts=True" in out.stdout
    assert "adopted=2" in out.stdout and "digest_eq=True" in out.stdout


def test_compile_cache_dir(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set; without it
    the cache goes to the fixed directory inside the checkout."""
    import jax
    from repro.launch import cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = cache.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
