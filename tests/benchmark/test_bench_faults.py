"""A run with the timed path broken underneath comes out not ``correct``:
once for each fault a cell can have (tiny sizes, CPU, the harness's
test-only override; the faults are planted by ``tiny_run.py``).  No cell
runs on more than one chip, so none can leave out an exchange between
chips."""
import pytest

from test_bench_cells import tiny_run

FAULTS = [
    ("qwen3-0.6b.block2", "frozen_state"),
    ("qwen3-0.6b.block2", "half_batch"),
    ("qwen3-0.6b.block2", "altered_loss"),
    ("qwen3-0.6b.block16", "frozen_state"),
    ("qwen3-0.6b.block16", "half_batch"),
    ("qwen3-0.6b.block16", "altered_loss"),
    ("pnpcoin-node.classic", "altered_winner"),
    ("pnpcoin-node.classic", "half_nonces"),
    ("pnpcoin-node.classic", "winner_half_only"),
]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_not_correct(cell, fault):
    line, _ = tiny_run(cell, fault)
    assert line["correct"] is False
    over = [k for k, c in line["checks"].items() if c["value"] > c["limit"]]
    assert over, line["checks"]
