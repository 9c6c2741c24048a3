"""The full-mode cell ``pnpcoin-mesh4.collatz_full`` at a tiny size
(2^12 args, four virtual CPU devices): its control and each fault of
``faults/pnpcoin-mesh4.py`` come out not ``correct``, each on the number
that has to catch it; every block's slice is its own, drawn from the
seed; and its plain reference agrees with a pure-Python Collatz and
``hashlib`` Merkle root."""
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from test_bench_cells import TINY_RUN, tiny_env, tiny_run

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench.reference import full as ref  # noqa: E402

CELL = "pnpcoin-mesh4.collatz_full"


def test_control_is_not_correct():
    out = subprocess.run([sys.executable, TINY_RUN, CELL, "control", "0"],
                         capture_output=True, text=True, env=tiny_env(CELL),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    checks = json.loads(out.stdout.strip().splitlines()[-1])
    assert checks["result_mismatch"]["value"] >= 1, checks
    assert checks["root_mismatch"]["value"] == 1, checks


@pytest.mark.parametrize("fault,numbers", [
    ("zeroed_tail", {"result_mismatch", "refused"}),
    ("lost_shards", {"result_mismatch", "refused"}),
    ("one_chip_miner", {"miner_chips_short"}),
    ("replayed_block", {"result_mismatch", "root_mismatch", "refused"}),
])
def test_fault_is_not_correct(fault, numbers):
    line, _ = tiny_run(CELL, fault)
    assert line["correct"] is False
    over = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert over & numbers, line["checks"]
    if fault == "one_chip_miner":
        assert line["checks"]["miner_chips_short"]["value"] == 3


def _collatz(a, max_steps):
    b, n = max(a, 1), 0
    while b != 1 and n < max_steps:
        b = b // 2 if b % 2 == 0 else (3 * b + 1) % 2 ** 32
        n += 1
    return n if b == 1 else max_steps


def _root(leaves):
    level = [hashlib.sha256(x).digest() for x in leaves]
    while len(level) > 1:
        if len(level) % 2:
            level.append(level[-1])
        level = [hashlib.sha256(level[i] + level[i + 1]).digest()
                 for i in range(0, len(level), 2)]
    return level[0].hex()


def test_every_block_has_a_slice_of_its_own_drawn_from_the_seed():
    from bench.harness import load_cell
    from bench.systems.jash_mesh import System
    cell = load_cell(CELL)
    a, b, c = (System(cell, s).slices for s in (2**31 + 5, 2**31 + 5, 9))
    assert (a == b).all() and not (a[:16] == c[:16]).all()
    n = cell.config["args_per_block"]
    assert len(set(a.tolist())) == len(a) == 2**32 // n
    assert (a % n == 0).all()


@pytest.mark.parametrize("max_steps,base", [
    (1024, 0), (64, 0), (7, 0), (1024, 0xFFFFFF00), (1024, 3 << 20)])
def test_reference_matches_plain_python(max_steps, base):
    bits = 8
    res, hashes, root = ref.full(bits, max_steps, base)
    want = [_collatz(base + a, max_steps) for a in range(1 << bits)]
    assert res[:, 0].tolist() == want
    for a in (0, 1, 27, 255):
        msg = a.to_bytes(4, "big") + want[a].to_bytes(4, "big")
        assert hashes[a].tolist() == list(np.frombuffer(
            hashlib.sha256(msg).digest(), ">u4"))
    assert root == _root([a.to_bytes(4, "little")
                          + r.to_bytes(4, "little")
                          for a, r in enumerate(want)])


def test_reference_wraps_like_uint32():
    """159,487 is a start whose path passes 2**32 (at
    17,202,377,752): the reference follows the wrapped uint32 path, as
    the jash does, and not the true one."""
    a = 159487
    true = _collatz_unbounded(a)
    wrapped = _collatz(a, 1024)
    assert wrapped != true
    assert int(ref.stopping_times(a + 1, 1024)[a]) == wrapped


def _collatz_unbounded(b):
    n = 0
    while b != 1:
        b = b // 2 if b % 2 == 0 else 3 * b + 1
        n += 1
    return n
