"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load`` keeps from it only what the metrics read: the device planes'
op and module events, and the host spans of the benchmark (names
starting with ``bench.``) and of the program (``repro.spans``: ``node.``,
``workload.``, ``executor.``, ``verify.`` and the rest of
``SPAN_PREFIXES``).  ``reduce`` then computes, inside the measured window:

* busy time per device: the union of the intervals in which an op ran;
* device time and count per program (module), by name;
* device time per op name (the breakdown's ``device_ops``);
* idle time, each stretch charged to the innermost host span, the
  benchmark's or the program's, open over it (the breakdown's
  ``idle_gaps``).

The window runs from the start of the first ``bench.block`` span to the
end of the ``n_blocks``-th: the blocks the harness counted.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "node.", "ra.", "workload.", "executor.",
                 "verify.", "ledger.", "store.", "params_digest",
                 "tree_digest.", "model_train.")
BLOCK_SPAN = "bench.block"

Interval = Tuple[float, float, str]          # (start_ns, end_ns, name)


@dataclasses.dataclass
class Trace:
    # plane name -> {"ops": [...], "modules": [...]}
    devices: Dict[str, Dict[str, List[Interval]]]
    spans: List[Interval]                     # host spans, SPAN_PREFIXES


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Interval]]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    lines[key].append((float(ev.start_ns),
                                       float(ev.start_ns + ev.duration_ns),
                                       ev.name))
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((float(ev.start_ns),
                                      float(ev.start_ns + ev.duration_ns),
                                      ev.name))
    return Trace(devices, sorted(spans))


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


_OP_KIND = re.compile(r"\b([a-z][a-z0-9\-_]*)\(")


def op_label(event_name: str) -> str:
    """An op event is named by its whole HLO instruction; keep its name
    and its kind: ``%fusion.518 = (bf16[...]) fusion(...)`` becomes
    ``%fusion.518 fusion``."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name
    kind = _OP_KIND.search(rest)
    return f"{head} {kind.group(1)}" if kind else head


def program_name(event_name: str) -> str:
    """A module event is named like ``jit_block(1234)``: drop the id."""
    return event_name.split("(", 1)[0]


@dataclasses.dataclass
class DeviceReduction:
    busy_s: float
    programs: Dict[str, Tuple[float, int]]    # name -> (seconds, count)
    ops: Dict[str, float]                      # op name -> seconds
    gaps: Dict[str, float]                     # host span -> idle seconds


@dataclasses.dataclass
class Reduction:
    window: Tuple[float, float]                # ns, trace clock
    devices: Dict[str, DeviceReduction]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def idle_share(self, plane: str) -> float:
        return 1.0 - self.devices[plane].busy_s / self.window_s

    def mean_busy_s(self) -> float:
        return sum(d.busy_s for d in self.devices.values()) / len(self.devices)

    def program_time(self, name: str) -> Tuple[float, int]:
        """Summed device seconds and event count of program ``name`` over
        every device, averaged over the devices."""
        tot, cnt = 0.0, 0
        for d in self.devices.values():
            s, c = d.programs.get(name, (0.0, 0))
            tot, cnt = tot + s, cnt + c
        n = len(self.devices)
        return tot / n, cnt // n

    def breakdown(self, top: int = 10) -> dict:
        n = len(self.devices)
        ops: Dict[str, float] = defaultdict(float)
        gaps: Dict[str, float] = defaultdict(float)
        for d in self.devices.values():
            for k, v in d.ops.items():
                ops[k] += v / n
            for k, v in d.gaps.items():
                gaps[k] += v / n
        rank = lambda m: [[k, v] for k, v in sorted(
            m.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}


def _innermost(spans: List[Interval], t: float) -> str:
    best: Optional[Interval] = None
    for s, e, n in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best is not None else "no benchmark span"


class _Spans:
    """Spans sorted by start, with the longest length, so that the spans
    over an interval are found by bisection."""

    def __init__(self, spans: List[Interval]) -> None:
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0.0)

    def over(self, g0: float, g1: float) -> List[Interval]:
        lo = bisect.bisect_left(self.starts, g0 - self.longest)
        hi = bisect.bisect_right(self.starts, g1)
        return [sp for sp in self.spans[lo:hi] if sp[1] > g0]


def _attribute(spans: _Spans, g0: float, g1: float,
               gaps: Dict[str, float]) -> None:
    """Split the idle interval [g0, g1] at every span edge inside it and
    charge each piece to the innermost span open over it."""
    near = spans.over(g0, g1)
    cuts = sorted({g0, g1} | {t for s, e, _ in near for t in (s, e)
                              if g0 < t < g1})
    for a, b in zip(cuts, cuts[1:]):
        gaps[_innermost(near, (a + b) / 2)] += (b - a) * 1e-9


def reduce(trace: Trace, n_blocks: int,
           devices: Optional[List[str]] = None) -> Reduction:
    """Reduce ``trace`` over the window of its first ``n_blocks``
    ``bench.block`` spans, on ``devices`` (default: every device plane)."""
    blocks = [sp for sp in trace.spans if sp[2] == BLOCK_SPAN]
    if len(blocks) < n_blocks or n_blocks < 1:
        raise ValueError(f"trace holds {len(blocks)} {BLOCK_SPAN} spans, "
                         f"need {n_blocks}")
    lo, hi = blocks[0][0], blocks[n_blocks - 1][1]
    spans = [sp for sp in trace.spans if sp[1] > lo and sp[0] < hi]
    index = _Spans(spans)
    names = devices if devices is not None else sorted(trace.devices)
    if not names:
        raise ValueError("trace holds no device plane")
    out: Dict[str, DeviceReduction] = {}
    for plane in names:
        lines = trace.devices[plane]
        ops_iv = lines["ops"] or lines["modules"]
        merged = _merge([(max(s, lo), min(e, hi)) for s, e, _ in ops_iv
                         if e > lo and s < hi])
        busy = sum(e - s for s, e in merged) * 1e-9
        programs: Dict[str, Tuple[float, int]] = {}
        for s, e, name in lines["modules"]:
            if lo <= s < hi:
                key = program_name(name)
                t, c = programs.get(key, (0.0, 0))
                programs[key] = (t + _clip(s, e, lo, hi) * 1e-9, c + 1)
        ops: Dict[str, float] = defaultdict(float)
        for s, e, name in lines["ops"]:
            if e > lo and s < hi:
                ops[op_label(name)] += _clip(s, e, lo, hi) * 1e-9
        gaps: Dict[str, float] = defaultdict(float)
        cursor = lo
        for s, e in merged + [(hi, hi)]:
            if s > cursor:
                _attribute(index, cursor, s, gaps)
            cursor = max(cursor, e)
        out[plane] = DeviceReduction(busy, programs, dict(ops), dict(gaps))
    return Reduction((lo, hi), out)
