"""Every cell of ``BENCHMARK.json`` end to end at a tiny size on the CPU,
through the harness's test-only override (each in a process of its own:
the harness sets JAX state for its whole process).  A sound run is
``correct`` under the cell's own limits and reports every end-to-end
metric the cell has."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY_RUN = os.path.join(HERE, "tiny_run.py")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def tiny_run(cell, fault="none"):
    # a tiny 16-microstep block takes 3 s alone, longer beside other tests
    seconds = "15" if cell.endswith("block16") else "4"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, TINY_RUN, cell, fault, seconds],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_tiny_size(cell):
    line, err = tiny_run(cell)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert want <= set(line["metrics"])
    assert list(line)[-1] == "checks"
    names = list(line["checks"])
    tail = err.strip().splitlines()[-len(names):]
    assert [t.split()[0] for t in tail] == names
