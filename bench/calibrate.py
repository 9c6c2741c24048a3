"""Readings from which the limits of ``correct`` are set; not part of a
benchmark run.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6 [--fault-seeds 7] [--witness-seeds 8] \\
        [--seconds 0]

For each of ``--seeds`` the program runs set-up (and a window of
``--seconds``, if given), is freed, and is compared with the reference:
one JSON line per seed.  For each of ``--control-seeds`` the control (the
reference in the program's place, one step down in precision or with
one guarantee broken) is compared the same way, and for each of
``--fault-seeds`` each fault the cell's driver can plant in the reference
(``faults()``, where it has one), and for each of ``--witness-seeds``
its second witness (``witness()``: the reference at the program's own
precision against the reference).  Everything runs in one
process, so the programs compile once.  The last line gives each
number's largest program reading and smallest control reading."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import gc  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402


def main(argv=None, override=None) -> int:
    import argparse
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--witness-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    fault_seeds = [int(s) for s in args.fault_seeds.split(",") if s]
    witness_seeds = [int(s) for s in args.witness_seeds.split(",") if s]
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    if override is None or override.compile_cache:
        harness._use_checkout_cache()
    platform = override.platform if override else "tpu"
    if harness.check_devices(cell.chips, platform) is None:
        return 3
    program, ctl = {}, {}
    for seed in seeds:
        t0 = time.perf_counter()
        _, system = harness.build(cell, seed, override)
        system.setup()
        if args.seconds:
            harness.window(system, args.seconds)
        system.release()
        gc.collect()
        got = system.readings()
        detail = getattr(system, "leaf_gaps", None)
        del system
        gc.collect()
        print(json.dumps({"seed": seed, "program": got, "detail": detail,
                          "s": time.perf_counter() - t0}), flush=True)
        for k, v in got.items():
            program[k] = max(program.get(k, v), v)
    for seed in control:
        t0 = time.perf_counter()
        _, system = harness.build(cell, seed, override)
        got = system.control()
        del system
        gc.collect()
        print(json.dumps({"seed": seed, "control": got,
                          "s": time.perf_counter() - t0}), flush=True)
        for k, v in got.items():
            ctl[k] = min(ctl.get(k, v), v)
    for seed in fault_seeds:
        t0 = time.perf_counter()
        _, system = harness.build(cell, seed, override)
        got = system.faults()
        del system
        gc.collect()
        print(json.dumps({"seed": seed, "faults": got,
                          "s": time.perf_counter() - t0}), flush=True)
    for seed in witness_seeds:
        t0 = time.perf_counter()
        _, system = harness.build(cell, seed, override)
        got = system.witness()
        del system
        gc.collect()
        print(json.dumps({"seed": seed, "witness": got,
                          "s": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"program_max": program, "control_min": ctl}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
