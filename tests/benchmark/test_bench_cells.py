"""Every cell of ``BENCHMARK.json`` end to end at a tiny size on the CPU,
through the harness's test-only override (each in a process of its own:
the harness sets JAX state for its whole process, and a cell on four
chips gets four virtual CPU devices).  A sound run is ``correct`` under
the cell's own limits and reports every end-to-end metric the cell has.
Each configuration's tiny sizes and seconds are its own file,
``tiny/<config>.json``."""
import json
import os
import subprocess
import sys

import pytest

from tiny_run import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY_RUN = os.path.join(HERE, "tiny_run.py")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
WORKLOADS = {w["name"]: w for w in BENCH["workloads"]}


def tiny_env(cell):
    """The CPU, with as many virtual devices as the cell asks chips."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    chips = WORKLOADS[cell]["chips"]
    if chips > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={chips}").strip()
    return env


def tiny_seconds(cell):
    wl = WORKLOADS[cell]
    return str(tiny(wl["config"])["seconds"][wl["traffic"]])


def tiny_run(cell, fault="none"):
    out = subprocess.run([sys.executable, TINY_RUN, cell, fault,
                          tiny_seconds(cell)],
                         capture_output=True, text=True, env=tiny_env(cell),
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


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_config_has_its_tiny_sizes(config):
    path = os.path.join(HERE, "tiny", config + ".json")
    assert os.path.exists(path), (
        f"configuration {config!r} has no tiny sizes: add {path} with its "
        "'config' and 'traffic' patches and 'seconds' per traffic")
    sizes = tiny(config)
    assert {"config", "traffic", "seconds"} <= set(sizes)
    for w in BENCH["workloads"]:
        if w["config"] == config:
            assert w["traffic"] in sizes["seconds"], (config, w["traffic"])
