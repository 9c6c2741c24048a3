"""``bench/trace.py`` keeps the program's own host spans beside the
benchmark's: idle gaps go to the innermost of either, while the window
(``bench.block`` spans), busy time and idle share read as they did with
the benchmark's spans alone."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import trace as tr  # noqa: E402

DEV = "/device:TPU:0"
MS = 1e6  # ns
PROBE = os.path.join(HERE, "data", "v5e_probe.xplane.pb")


def _bench_only(trace):
    return tr.Trace(trace.devices, [s for s in trace.spans
                                    if s[2].startswith("bench.")])


def test_probe_window_busy_and_idle_unchanged():
    trace = tr.load(PROBE)
    for n in (1, 3, 4):
        got, was = tr.reduce(trace, n), tr.reduce(_bench_only(trace), n)
        assert got.window == was.window
        assert got.devices[DEV].busy_s == was.devices[DEV].busy_s
        assert got.idle_share(DEV) == was.idle_share(DEV)
        assert sum(got.devices[DEV].gaps.values()) == pytest.approx(
            sum(was.devices[DEV].gaps.values()))


def test_program_spans_are_kept_and_others_dropped():
    names = ["node.mine_block", "workload.verify", "executor.run_full.chunk",
             "verify.quorum_verify", "ledger.merkle_root", "params_digest",
             "tree_digest.hash", "model_train.step", "ra.publish",
             "store.append_commit", "bench.block"]
    for name in names:
        assert name.startswith(tr.SPAN_PREFIXES), name
    for name in ("PjitFunction(eval_chunk)", "ThunkExecutor::Execute"):
        assert not name.startswith(tr.SPAN_PREFIXES), name


def test_gaps_go_to_the_innermost_program_span():
    """One 10 ms block: the mine (0-6 ms) holds ``node.mine_block``,
    which holds a chunk dispatch (1-3 ms, busy 1-2) and the root
    (4-6 ms); the receive (6-10 ms) holds ``node.receive``."""
    spans = [(0, 10 * MS, "bench.block"), (0, 6 * MS, "bench.mine_block"),
             (6 * MS, 10 * MS, "bench.receive"),
             (0.5 * MS, 6 * MS, "node.mine_block"),
             (1 * MS, 3 * MS, "executor.run_full.chunk"),
             (4 * MS, 6 * MS, "ledger.merkle_root"),
             (6.5 * MS, 9.5 * MS, "node.receive")]
    ops = [(1 * MS, 2 * MS, "while.3")]
    trace = tr.Trace({DEV: {"ops": ops, "modules": []}}, sorted(spans))
    red = tr.reduce(trace, 1)
    gaps = dict(red.breakdown()["idle_gaps"])
    assert gaps["executor.run_full.chunk"] == pytest.approx(1e-3)
    assert gaps["ledger.merkle_root"] == pytest.approx(2e-3)
    assert gaps["node.mine_block"] == pytest.approx(1.5e-3)   # 0.5-1, 3-4
    assert gaps["bench.mine_block"] == pytest.approx(0.5e-3)
    assert gaps["node.receive"] == pytest.approx(3e-3)
    assert gaps["bench.receive"] == pytest.approx(1e-3)
    assert red.devices[DEV].busy_s == pytest.approx(1e-3)
    assert sum(gaps.values()) == pytest.approx(red.window_s - 1e-3)


def test_harness_traces_spans_without_the_python_tracer(tmp_path):
    """The harness's profiler options keep host spans and drop the
    Python tracer, which would record every call of a ``hashlib`` loop."""
    import hashlib

    import jax
    from jax.profiler import ProfileData

    from bench.harness import _profile_options
    options = _profile_options()
    assert options.python_tracer_level == 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.block"):
        with jax.profiler.TraceAnnotation("ledger.merkle_root"):
            for i in range(20000):
                hashlib.sha256(i.to_bytes(4, "little")).digest()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert [s[2] for s in tr.load(path).spans] == ["bench.block",
                                                   "ledger.merkle_root"]
    # the Python tracer names each call it records "$<module> <function>"
    calls = [ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("$")]
    assert calls == []
