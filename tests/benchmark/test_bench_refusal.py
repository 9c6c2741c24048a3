"""``bench/run.py`` prints no result and exits non-zero where it cannot
measure: on the CPU, and in a directory holding only ``BENCHMARK.json``
and the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def _run(root, tmp_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(tmp_env or {}))
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "pnpcoin-node.classic", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=root, timeout=300)


def _no_result(out):
    assert out.returncode != 0
    for line in out.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in obj


def test_refuses_the_cpu():
    out = _run(REPO)
    _no_result(out)
    assert "no result" in out.stderr


def test_refuses_without_the_program(tmp_path):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(str(tmp_path)))
