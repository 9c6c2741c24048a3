"""The readers of the per-layer metrics that come from the program's own
spans (``bench/program_spans.py``), on a hand-built ``RunContext`` whose
block records bracket spans of a real CPU mine: a number inside the
window, nothing outside it, nothing where the ring lost records of the
window, and nothing from a program without ``repro.spans``."""
import math
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "src"))

import repro  # noqa: E402
from bench.harness import BlockRecord, RunContext, load_reader  # noqa: E402
from repro import spans  # noqa: E402
from repro.chain import ChainStore, Node  # noqa: E402
from repro.chain.workloads.model_train import (  # noqa: E402
    MICRO_KWARGS, ModelTrainingWorkload)

TRAIN = ["params_digest.ms_per_block", "params_digest.fetch_gb_per_s",
         "params_digest.hash_gb_per_s", "batch_build.ms_per_block",
         "proof_rows.ms_per_block", "mine_block.unspanned_share"]
CLASSIC = ["classic_mine.host_us_per_block", "node.block_p95_ms"]
CLASSIC_BLOCKS = 24                   # node.block_p95_ms wants 20 or more


def _ctx(records):
    return RunContext(cell=None, system=None, records=records,
                      window_s=records[-1].end - records[0].start,
                      device_kind="cpu")


def _mine_train():
    node = Node(node_id=0, snapshot_interval=0, store=ChainStore(),
                workloads={"model_train":
                           ModelTrainingWorkload(**MICRO_KWARGS)})
    node.mine_block("model_train")              # compiles, left out
    out = []
    for _ in range(3):
        t0 = time.perf_counter()
        node.mine_block("model_train")
        out.append(BlockRecord(t0, time.perf_counter(), True, {}))
    return out


def _mine_classic():
    miner, verifier = (Node(node_id=i, classic_arg_bits=6) for i in (0, 1))
    out = []
    for _ in range(CLASSIC_BLOCKS):
        t0 = time.perf_counter()
        r = miner.mine_block(None)
        ok = verifier.receive(r.record.to_block(), r.payload, origin=0)
        out.append(BlockRecord(t0, time.perf_counter(), ok, {}))
    return out


@pytest.fixture(scope="module")
def windows():
    t = time.perf_counter()
    before = [BlockRecord(t - 2.0, t - 1.0, True, {})]
    return {"train": _mine_train(), "classic": _mine_classic(),
            "before": before}


def _cell(metric):
    return "train" if metric in TRAIN else "classic"


@pytest.mark.parametrize("metric", TRAIN + CLASSIC)
def test_a_number_inside_the_window(windows, metric):
    value = load_reader(metric)(_ctx(windows[_cell(metric)]))
    assert value is not None and math.isfinite(value) and value >= 0
    if metric == "mine_block.unspanned_share":
        assert value <= 10.0
    elif metric != "classic_mine.host_us_per_block":
        assert value > 0


@pytest.mark.parametrize("metric", TRAIN + CLASSIC)
def test_nothing_outside_the_window(windows, metric):
    assert load_reader(metric)(_ctx(windows["before"])) is None


def _drop_until(monkeypatch, end_ns):
    """The ring as if it had lost every record that closed before
    ``end_ns``."""
    kept = [r for r in spans.records() if r.end_ns >= end_ns]
    lost = len(spans.records()) - len(kept)
    base = spans.dropped()
    monkeypatch.setattr(spans, "records", lambda since_ns=None: list(kept))
    monkeypatch.setattr(spans, "dropped", lambda: base + lost)
    return lost


@pytest.mark.parametrize("metric", TRAIN + CLASSIC)
def test_nothing_where_the_ring_lost_records_of_the_window(
        windows, metric, monkeypatch):
    recs = windows[_cell(metric)]
    inside = round((recs[0].start + recs[0].end) / 2 * 1e9)
    assert _drop_until(monkeypatch, inside) > 0
    assert load_reader(metric)(_ctx(recs)) is None


@pytest.mark.parametrize("metric", TRAIN + CLASSIC)
def test_a_loss_before_the_window_keeps_the_number(windows, metric,
                                                   monkeypatch):
    recs = windows[_cell(metric)]
    read = load_reader(metric)
    whole = read(_ctx(recs))
    lo = round(recs[0].start * 1e9)
    # keep the last record that closed before the window: it shows that
    # nothing after it was lost
    last = max(r.end_ns for r in spans.records() if r.end_ns < lo)
    assert _drop_until(monkeypatch, last) > 0
    assert read(_ctx(recs)) == whole


@pytest.mark.parametrize("metric", TRAIN + CLASSIC)
def test_nothing_from_a_program_without_spans(windows, metric,
                                              monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    monkeypatch.delattr(repro, "spans")
    assert load_reader(metric)(_ctx(windows[_cell(metric)])) is None


def test_span_latency_lies_inside_the_harness_latency(windows):
    """Each height's span interval lies inside its block record, so the
    spans' p95 cannot pass the harness's."""
    ctx = _ctx(windows["classic"])
    inner = load_reader("node.block_p95_ms")(ctx)
    outer = load_reader("block_p95_ms")(ctx)
    assert 0 < inner <= outer


def test_params_digest_fits_in_its_blocks(windows):
    ctx = _ctx(windows["train"])
    longest = max(r.end - r.start for r in ctx.records) * 1e3
    for metric in ("params_digest.ms_per_block", "batch_build.ms_per_block",
                   "proof_rows.ms_per_block"):
        assert 0 < load_reader(metric)(ctx) < longest
