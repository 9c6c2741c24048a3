"""The readers of the full-mode cell's per-layer metrics, on a
hand-built ``RunContext`` around tiny full blocks mined and received on
the CPU.  Span readers: a number inside the window, nothing outside it,
nothing where the ring lost records of the window, nothing from a
program without ``repro.spans``.  Device-trace readers: a number from a
trace that holds the chunk program, nothing from one that does not, and
nothing from an untraced run."""
import math
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "src"))

import repro  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.harness import BlockRecord, RunContext, load_reader  # noqa: E402
from bench.systems.jash_mesh import collatz  # noqa: E402
from repro.chain import Node  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from test_bench_spans import _drop_until  # noqa: E402

SPAN_METRICS = ["run_full.ms_per_block", "quorum_verify.ms_per_block",
                "full_root.ms_per_block"]
TRACE_METRICS = ["full_chunk.ns_per_arg", "idle_share.full"]
ARG_BITS = 8
MS = 1e6  # ns


def _ctx(records, reduction=None):
    system = types.SimpleNamespace(args_per_block=1 << ARG_BITS)
    return RunContext(cell=None, system=system, records=records,
                      window_s=records[-1].end - records[0].start,
                      device_kind="cpu", reduction=reduction)


@pytest.fixture(scope="module")
def windows():
    jash = collatz(ARG_BITS, 1024)
    miner = Node(node_id=0, mesh=make_host_mesh())
    verifier = Node(node_id=1)
    out = []
    for _ in range(4):
        t0 = time.perf_counter()
        miner.submit(jash)
        r = miner.mine_block("full")
        ok = verifier.receive(r.record.to_block(), r.payload, origin=0)
        out.append(BlockRecord(t0, time.perf_counter(), ok, {}))
    t = time.perf_counter()
    return {"full": out[1:],               # the first compiles: left out
            "before": [BlockRecord(t - 2.0, t - 1.0, True, {})]}


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_a_number_inside_the_window(windows, metric):
    ctx = _ctx(windows["full"])
    value = load_reader(metric)(ctx)
    longest = max(r.end - r.start for r in ctx.records) * 1e3
    assert value is not None and math.isfinite(value)
    assert 0 < value < longest


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_nothing_outside_the_window(windows, metric):
    assert load_reader(metric)(_ctx(windows["before"])) is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_nothing_where_the_ring_lost_records_of_the_window(
        windows, metric, monkeypatch):
    recs = windows["full"]
    inside = round((recs[0].start + recs[0].end) / 2 * 1e9)
    assert _drop_until(monkeypatch, inside) > 0
    assert load_reader(metric)(_ctx(recs)) is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_nothing_from_a_program_without_spans(windows, metric,
                                              monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    monkeypatch.delattr(repro, "spans")
    assert load_reader(metric)(_ctx(windows["full"])) is None


def test_both_nodes_audit_each_block(windows):
    """The audit metrics sum both nodes: each is more than the miner's
    self-check alone."""
    from bench import program_spans
    ctx = _ctx(windows["full"])
    w = program_spans.window(ctx)
    mine_only = w.median_ms("node.mine_block", "verify.quorum_verify")
    assert load_reader("quorum_verify.ms_per_block")(ctx) > mine_only


def _mesh_trace(chunk_devices):
    """Two blocks of 10 ms; in each, one 2 ms chunk program on each of
    ``chunk_devices``, and a 1 ms other program on every device."""
    devices = {f"/device:TPU:{d}": {"ops": [], "modules": []}
               for d in range(4)}
    spans_ = []
    for b in range(2):
        t = b * 10 * MS
        spans_ += [(t, t + 10 * MS, "bench.block"),
                   (t, t + 6 * MS, "bench.mine_block")]
        for d, dev in enumerate(devices.values()):
            dev["modules"].append((t + 7 * MS, t + 8 * MS, f"jit_x({b})"))
            dev["ops"].append((t + 7 * MS, t + 8 * MS, "fusion.1"))
            if d in chunk_devices:
                dur = (2 + d) * MS
                dev["modules"].append((t + 1 * MS, t + 1 * MS + dur,
                                       f"jit_eval_chunk({b})"))
                dev["ops"].append((t + 1 * MS, t + 1 * MS + dur, "while.3"))
    return tr.reduce(tr.Trace(devices, sorted(spans_)), 2)


def test_chunk_time_per_arg_is_read_on_the_busiest_chip(windows):
    red = _mesh_trace(chunk_devices=range(4))
    got = load_reader("full_chunk.ns_per_arg")(_ctx(windows["full"][:2],
                                                    red))
    # busiest chip: device 3, 5 ms a block, over 2 blocks of 2**8 args
    assert got == pytest.approx(10e-3 / (2 * (1 << ARG_BITS)) * 1e9)


@pytest.mark.parametrize("metric", TRACE_METRICS)
def test_trace_readers_read_a_trace(windows, metric):
    red = _mesh_trace(chunk_devices=range(4))
    value = load_reader(metric)(_ctx(windows["full"][:2], red))
    assert value is not None and 0 < value < 100 * (1 << ARG_BITS)


def test_no_chunk_time_from_a_trace_without_the_chunk_program(windows):
    red = _mesh_trace(chunk_devices=())
    assert load_reader("full_chunk.ns_per_arg")(
        _ctx(windows["full"][:2], red)) is None


@pytest.mark.parametrize("metric", TRACE_METRICS)
def test_trace_readers_read_nothing_untraced(windows, metric):
    assert load_reader(metric)(_ctx(windows["full"])) is None
