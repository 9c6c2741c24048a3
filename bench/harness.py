"""The closed-loop block harness behind ``bench/run.py``.

One run of one cell:

1. read the cell, its configuration and its traffic mix by the names in
   ``BENCHMARK.json``, and the limits of its comparison
   (``cells/<cell>.json``);
2. refuse to run without the chips the cell asks for;
3. set-up: the cell's system driver builds the nodes, warms every
   program the window will run and drives the first blocks
   (``setup_s``, from process start to the window);
4. the window: blocks back to back until one finishes past ``--seconds``;
   that one is left out, so the window holds whole blocks only and runs
   from the first block's start to the last counted block's end;
5. with ``--trace 1`` the window runs under the profiler and the
   per-layer metrics are read from the trace, otherwise the end-to-end
   metrics from the host clock;
6. the program's state is freed and the comparison that decides
   ``correct`` runs: each number beside its limit, on standard error and
   under ``checks`` in the result line, which is the last line printed.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: Dict[str, float]


@dataclasses.dataclass
class BlockRecord:
    start: float                    # host clock, perf_counter seconds
    end: float
    accepted: bool
    units: Dict[str, float]         # work in the block: tokens, hashes ...


@dataclasses.dataclass
class Override:
    """Test-only knobs; the command line has none of them."""
    platform: str = "tpu"
    config: Dict[str, Any] = dataclasses.field(default_factory=dict)
    traffic: Dict[str, Any] = dataclasses.field(default_factory=dict)
    compile_cache: bool = True


@dataclasses.dataclass
class RunContext:
    """What a metric reader sees."""
    cell: Cell
    system: Any
    records: List[BlockRecord]
    window_s: float
    device_kind: str
    reduction: Any = None           # trace.Reduction, with --trace 1


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _merge(base: dict, patch: dict) -> dict:
    out = dict(base)
    for k, v in patch.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    wl = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    config.setdefault("name", entry["name"])
    with open(os.path.join(BENCH_DIR, "traffic", wl["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    # a metric with no "workloads" key is read in every cell that reports
    # the end-to-end metric it moves, cells that later entries add too
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [])
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    limits_path = os.path.join(BENCH_DIR, "cells", name + ".json")
    limits = {}
    if os.path.exists(limits_path):
        with open(limits_path) as f:
            limits = {k: v["limit"] for k, v in json.load(f)["limits"].items()}
    return Cell(name, wl["chips"], config, traffic, e2e, per_layer, limits)


def load_reader(metric: str) -> Callable[[RunContext], Optional[float]]:
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _use_checkout_cache() -> None:
    """The persistent compilation cache goes where the program's
    ``enable_compile_cache`` puts it when the environment names no
    directory: ``.jax_cache`` at the root of the checkout, a fixed path.
    A directory named in the environment is set aside, so that the cache
    stays inside the checkout.  Every program is kept."""
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import jax
    from repro.launch.cache import enable_compile_cache
    os.makedirs(enable_compile_cache(), exist_ok=True)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _profile_options():
    """Host spans and device ops, without the profiler's Python tracer
    (on by default), which records every Python call: it makes code of
    many small calls, such as a ``hashlib`` Merkle root, three times
    slower in a traced run and writes hundreds of MB a block."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    return options


def _say(*parts) -> None:
    print(*parts, flush=True)


def parse_args(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def build(cell: Cell, seed: int, override: Optional[Override]):
    if override is not None:
        cell = dataclasses.replace(
            cell, config=_merge(cell.config, override.config),
            traffic=_merge(cell.traffic, override.traffic))
    module = importlib.import_module("bench.systems." + cell.config["system"])
    return cell, module.System(cell, seed)


def check_devices(chips: int, platform: str):
    import jax
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        print(f"need {chips} {platform} device(s), found {len(devs)} "
              f"{devs[0].platform} device(s): no result", file=sys.stderr)
        return None
    return devs


def window(system, seconds: float) -> List[BlockRecord]:
    records: List[BlockRecord] = []
    start = time.perf_counter()
    while True:
        with annotate("bench.block"):
            rec = system.block()
        if rec.end - start > seconds:
            break
        records.append(rec)
    if not records:
        raise RuntimeError(f"no block finished within {seconds} s")
    return records


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def main(argv=None, *, t0: Optional[float] = None,
         override: Optional[Override] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    cell = load_cell(args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    if override is None or override.compile_cache:
        _use_checkout_cache()
    import jax
    devs = check_devices(cell.chips, override.platform if override else "tpu")
    if devs is None:
        return 3
    from bench.compile_clock import CompileClock
    clock = CompileClock()
    cell, system = build(cell, args.seed, override)
    system.setup()
    setup_s = time.perf_counter() - t0 - system.capture_s
    compiles0 = clock.compiles

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR,
                                 profiler_options=_profile_options())
    try:
        records = window(system, args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    in_window = clock.compiles - compiles0
    used = [devs[i] for i in system.trace_devices]
    peak = peak_bytes(used)
    win_s = records[-1].end - records[0].start
    _say(f"[window] blocks={len(records)} window_s={win_s!r} "
         f"compiles_in_window={in_window} setup_s={setup_s!r} "
         f"setup_compiles={compiles0} cache_hits={clock.cache_hits}")
    _say(f"[memory] peak_bytes_in_use={peak}")

    ctx = RunContext(cell, system, records, win_s, devs[0].device_kind)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {}
    if args.trace:
        from bench import trace as tr
        loaded = tr.load(tr.find_xplane(TRACE_DIR))
        planes = sorted(loaded.devices)
        red = tr.reduce(loaded, len(records),
                        [p for p in planes
                         if int(p.rsplit(":", 1)[1]) in system.trace_devices])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx.reduction = red
        for plane in sorted(red.devices):
            _say(f"[trace] device={plane} busy_s={red.devices[plane].busy_s!r}"
                 f" idle_share={red.idle_share(plane)!r}")
        device["busy_s"] = red.mean_busy_s()
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()

    metrics: Dict[str, Dict[str, Any]] = {}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    for m in wanted:
        value = setup_s if m["name"] == "setup_s" else \
            load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    system.release()
    gc.collect()
    readings = system.readings()
    checks = {k: {"value": v, "limit": cell.limits.get(k)}
              for k, v in readings.items()}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    failed = sum(not r.accepted for r in records)
    line = {"correct": correct, "attempted": len(records), "failed": failed,
            "metrics": metrics, "device": device}
    line.update(result)
    line["checks"] = checks
    _say(json.dumps(line))
    for k, c in checks.items():
        print(f"{k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr,
              flush=True)
    return 0
