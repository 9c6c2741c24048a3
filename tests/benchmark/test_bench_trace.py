"""``bench/trace.py``: the reduction from a profiler trace to busy time,
program time, idle gaps and the breakdown — on a hand-built trace whose
answers are known, and on a small trace recorded on a TPU v5e
(``data/v5e_probe.xplane.pb``: four ``bench.block`` spans, each a matmul
program under ``bench.mine_block`` and an elementwise one under
``bench.receive``)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import trace as tr  # noqa: E402

DEV = "/device:TPU:0"
MS = 1e6  # ns


def _hand_trace():
    spans = []
    for i in range(3):                       # blocks of 10 ms
        b = i * 10 * MS
        spans += [(b, b + 10 * MS, "bench.block"),
                  (b, b + 6 * MS, "bench.mine_block"),
                  (b + 6 * MS, b + 10 * MS, "bench.receive")]
    ops, modules = [], []
    for i in range(3):
        b = i * 10 * MS
        modules.append((b + 1 * MS, b + 4 * MS, f"jit_block({i})"))
        ops += [(b + 1 * MS, b + 3 * MS, "fusion.1"),
                (b + 2 * MS, b + 4 * MS, "fusion.2")]   # overlaps: union 3
    return tr.Trace({DEV: {"ops": ops, "modules": modules}}, sorted(spans))


def test_busy_is_a_union_of_intervals():
    red = tr.reduce(_hand_trace(), 2)        # window: first two blocks
    assert red.window_s == pytest.approx(20e-3)
    assert red.devices[DEV].busy_s == pytest.approx(6e-3)
    assert red.idle_share(DEV) == pytest.approx(0.7)


def test_program_time_and_count():
    red = tr.reduce(_hand_trace(), 3)
    seconds, count = red.program_time("jit_block")
    assert count == 3 and seconds == pytest.approx(9e-3)
    assert tr.program_name("jit_block(17)") == "jit_block"


def test_gaps_go_to_the_innermost_open_span():
    red = tr.reduce(_hand_trace(), 3)
    gaps = dict(red.breakdown()["idle_gaps"])
    # each block: 1 ms before the ops and 2 ms after them under
    # mine_block, 4 ms under receive
    assert gaps["bench.mine_block"] == pytest.approx(9e-3)
    assert gaps["bench.receive"] == pytest.approx(12e-3)
    ops = dict(red.breakdown()["device_ops"])
    assert ops["fusion.1"] == pytest.approx(6e-3)


def test_window_needs_its_blocks():
    with pytest.raises(ValueError):
        tr.reduce(_hand_trace(), 4)


def test_recorded_v5e_trace():
    path = os.path.join(HERE, "data", "v5e_probe.xplane.pb")
    trace = tr.load(path)
    assert sorted(trace.devices) == [DEV]
    red = tr.reduce(trace, 3)
    dev = red.devices[DEV]
    assert 0 < dev.busy_s < red.window_s
    mm, n_mm = red.program_time("jit_matmul")
    ew, n_ew = red.program_time("jit_scale")
    assert n_mm == 3 and n_ew == 3
    assert mm > ew > 0
    assert mm + ew <= dev.busy_s * 1.001
    gaps = dict(red.breakdown()["idle_gaps"])
    assert set(gaps) <= {"bench.block", "bench.mine_block", "bench.receive",
                         "no benchmark span"}
    assert sum(gaps.values()) == pytest.approx(red.window_s - dev.busy_s)


def test_op_labels_keep_name_and_kind():
    name = ("%fusion.518 = (bf16[151936,1024]{1,0:T(8,128)(2,1)}, "
            "f32[151936,1024]{1,0:T(8,128)}) fusion(bf16[151936,1024] "
            "%get-tuple-element.4724), kind=kLoop")
    assert tr.op_label(name) == "%fusion.518 fusion"
    assert tr.op_label("%while.3 = (s32[]{:T(128)}) while((s32[]) %t)") == \
        "%while.3 while"
    assert tr.op_label("fusion.1") == "fusion.1"
