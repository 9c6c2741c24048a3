"""One benchmark cell at a tiny size on the CPU, for the tests here.

    python tests/benchmark/tiny_run.py <cell> <fault|none|control> <seconds>

Runs ``bench/run.py``'s harness through its test-only ``Override`` (tiny
sizes, the CPU platform, no compile cache) in a process of its own,
with ``fault`` planted in the program's timed path first.  ``control``
prints the cell's control readings against its limits instead.

Each configuration brings its tiny sizes as ``tiny/<config>.json``: the
``config`` and ``traffic`` patches and the run's ``seconds`` per
traffic.  A fault that is not in ``FAULTS`` is looked up in the
configuration's own ``faults/<config>.py``, a function of that name."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "src"))


def tiny(config: str) -> dict:
    """The tiny sizes of configuration ``config``."""
    with open(os.path.join(HERE, "tiny", config + ".json")) as f:
        return json.load(f)


def _classic_bits() -> int:
    return tiny("pnpcoin-node")["config"]["classic_arg_bits"]


# -- faults planted in the program's timed path -----------------------------

def _wrap_block_step(make):
    import repro.chain.workloads.model_train as mt
    orig = mt._block_step

    def patched(cfg, hp, n_micro, mesh):
        return make(orig(cfg, hp, n_micro, mesh))

    mt._block_step = patched


def frozen_state():
    """The block step returns the state it was given."""
    def make(step):
        def block(state, batches):
            return state, step(state, batches)[1]
        return block
    _wrap_block_step(make)


def half_batch():
    """Each microstep trains on the first half of its rows, the mean loss
    taken over those."""
    import jax

    def make(step):
        def block(state, batches):
            return step(state, jax.tree.map(
                lambda x: x[:, : x.shape[1] // 2], batches))
        return block
    _wrap_block_step(make)


def altered_loss():
    """Each microstep's loss is reported 0.1% high where it is made."""
    def make(step):
        def block(state, batches):
            new, metrics = step(state, batches)
            return new, dict(metrics, loss=metrics["loss"] * 1.001)
        return block
    _wrap_block_step(make)


def altered_winner():
    """The search returns a nonce next to the winning one."""
    import repro.chain.workload as w
    orig = w.run_optimal

    def patched(jash, **kw):
        opt = orig(jash, **kw)
        return dataclasses.replace(opt, best_arg=opt.best_arg ^ 1)
    w.run_optimal = patched


def half_nonces():
    """Half of the nonce space is never searched: the half that holds the
    reference's winner (for the other half see ``winner_half_only``)."""
    import jax.numpy as jnp
    import repro.chain.workload as w
    from repro.core.executor import MAXW
    from repro.core.jash import Jash
    from bench.reference import jash as ref
    orig = w.run_optimal
    bits = _classic_bits()
    winner = ref.classic(bits)[0]
    n = 1 << bits
    lo = 0 if winner < n // 2 else n // 2
    cache = {}

    def patched(jash, **kw):
        fn = cache.get(jash.fn)
        if fn is None:
            base = jash.fn

            def fn(a):
                out = base(a)
                skip = (a >= lo) & (a < lo + n // 2)
                return jnp.where(skip, jnp.full_like(out, MAXW), out)
            cache[jash.fn] = fn
        return orig(Jash(jash.name, fn, jash.meta,
                         example_args=jash.example_args), **kw)
    w.run_optimal = patched


def winner_half_only():
    """Only the half of the nonce space that holds the reference's winner
    is searched, the other half left out: the block's answer, hash and
    root all come out right, and the result still claims every nonce."""
    import repro.chain.workload as w
    from repro.core.jash import Jash
    from bench.reference import jash as ref
    orig = w.run_optimal
    bits = _classic_bits()
    n = 1 << bits
    lo = 0 if ref.classic(bits)[0] < n // 2 else n // 2
    cache = {}

    def patched(jash, **kw):
        half = cache.get(jash.fn)
        if half is None:
            base = jash.fn
            half = cache[jash.fn] = Jash(
                jash.name, lambda a: base(a + lo),
                dataclasses.replace(jash.meta,
                                    arg_bits=jash.meta.arg_bits - 1),
                example_args=jash.example_args)
        opt = orig(half, **kw)
        return dataclasses.replace(opt, best_arg=opt.best_arg + lo,
                                   n_evaluated=n)
    w.run_optimal = patched


FAULTS = {f.__name__: f for f in (frozen_state, half_batch, altered_loss,
                                  altered_winner, half_nonces,
                                  winner_half_only)}


def fault(config: str, name: str):
    """Fault ``name``: one of ``FAULTS``, or else a function of
    ``faults/<config>.py``, loaded by path."""
    if name in FAULTS:
        return FAULTS[name]
    path = os.path.join(HERE, "faults", config + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_faults_" + config.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, name)


def override(cell_name: str):
    from bench.harness import Override, load_cell
    cell = load_cell(cell_name)
    t = tiny(cell.config["name"])
    return (Override(platform="cpu", compile_cache=False,
                     config=t["config"], traffic=t["traffic"]), cell)


def main(argv) -> int:
    cell_name, planted, seconds = argv
    ov, cell = override(cell_name)
    if planted == "control":
        from bench import harness
        _, system = harness.build(cell, 7, ov)
        got = system.control()
        print(json.dumps({k: {"value": v, "limit": cell.limits.get(k)}
                          for k, v in got.items()}))
        return 0
    if planted != "none":
        fault(cell.config["name"], planted)()
    from bench.harness import main as run
    return run(["--workload", cell_name, "--seed", "2147483901",
                "--seconds", seconds, "--trace", "0"], override=ov)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
