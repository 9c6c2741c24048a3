"""Each configuration's control (the reference in the program's place,
one precision step down or with one stated guarantee broken) fails at
least one of its cell's limits, at a tiny size on the CPU.

``qwen3-0.6b.block16`` runs the same control code as block2; its float8
readings at the sizes a CPU test holds (widths 64-256, 2-4 layers) fall
under block16's limits, which were set at the published widths, where
the control fails on every seed tried on the chip."""
import json
import os
import subprocess
import sys

import pytest

from test_bench_cells import TINY_RUN

CELLS = ["qwen3-0.6b.block2", "pnpcoin-node.classic"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, TINY_RUN, cell, "control", "0"],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    checks = json.loads(out.stdout.strip().splitlines()[-1])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks
