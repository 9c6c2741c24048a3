#!/usr/bin/env python3
"""Run the chain's main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: phases 1-4
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

One chip, one phase after another:

1. device  — platform, device kind, device count, jax/libtpu versions.
   Anything but a TPU is a failure.
2. jash    — a 2-node ``Network``: node 0 mines one full and one optimal
   block of the paper's Collatz jash and two classic double-SHA-256
   blocks.  No peer may reject a block, the network must converge, and
   ``audit_chain`` must pass on both nodes.
3. sha256  — the Pallas SHA-256 kernel, compiled for the chip, on 4096
   80-byte headers; bit-exact against the jnp path and ``hashlib``.
4. model   — real-model PoUW at qwen3-0.6b's published widths: a
   journaling ``Node`` mines blocks of ``ModelTrainingWorkload``, the
   miner is dropped, and ``Node.recover`` replays the journal on a fresh
   node; params digest, block hashes and credit books must be identical.

``--chips 4`` runs instead the paths that exist only across chips, each
against a plain one-chip node in the same process: a full block mined
over a 4-device ``("data",)`` mesh (``shard_map``) must verify on the
plain node, and qwen3-0.6b blocks mined with FSDP over that mesh must
replay to the same params digest on one chip.

Earlier lines say what each phase found.  The last line is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``; any
failure exits non-zero without it.  The process keeps JAX's persistent
compilation cache (``repro.launch.cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
from typing import Any, NamedTuple, Optional

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.chain import ChainStore, Network, Node  # noqa: E402
from repro.chain.workloads.model_train import ModelTrainingWorkload  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.jash import Jash, JashMeta, collatz_jash  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.ops import sha256_words  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke run drives.  The defaults are the chip run; the
    test suite passes a tiny instance (CPU platform, Pallas interpreter)
    to ``run`` directly — the command line has no way to change them."""
    platform: str = "tpu"            # the only platform phase 1 accepts
    interpret: bool = False          # Pallas interpreter: CPU tests only
    jash_arg_bits: int = 16
    classic_arg_bits: int = 20       # classic_jash's own default, 2^20
    n_headers: int = 4096
    model: Any = "qwen3-0.6b"
    n_layers: Optional[int] = None   # None keeps every layer
    seq_len: int = 512
    batch: int = 4
    microsteps: int = 2
    n_model_blocks: int = 3


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class _CompileClock:
    """Seconds the backend spends compiling (cache hits included), summed
    from JAX's own monitoring events."""
    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self._EVENT:
            self.total += duration


def _collatz(arg_bits: int) -> Jash:
    base = collatz_jash()
    return Jash(base.name, base.fn,
                JashMeta(arg_bits=arg_bits, res_bits=32, importance=0.8,
                         description="Collatz stopping times"),
                example_args=base.example_args)


def _peak_bytes() -> Any:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(sizes: Sizes, chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = "absent"
    _say("device", platform=dev["platform"], kind=repr(dev["kind"]),
         count=dev["count"], jax=jax.__version__, libtpu=libtpu)
    _check(dev["platform"] == sizes.platform,
           f"platform {dev['platform']!r}, need {sizes.platform!r}")
    _check(dev["count"] >= chips, f"{dev['count']} device(s), need {chips}")
    return dev


def phase_jash(sizes: Sizes, clock: _CompileClock) -> None:
    net = Network.create(2, classic_arg_bits=sizes.classic_arg_bits)
    schedule = ["full", "optimal", "classic", "classic"]
    for i, wl in enumerate(schedule):
        if wl in ("full", "optimal"):
            net.nodes[0].submit(_collatz(sizes.jash_arg_bits))
        c0, t0 = clock.total, time.perf_counter()
        res = net.mine(0, wl)
        dt = time.perf_counter() - t0
        _say("jash", block=i, workload=wl, n_results=res.receipt.payload
             .n_results, wall_s=f"{dt:.3f}", compile_s=f"{clock.total - c0:.3f}")
        _check(not res.rejected_by, f"{wl} block {i} rejected by "
               f"{res.rejected_by}")
    _check(net.converged(), "network did not converge")
    for node in net.nodes:
        _check(node.audit_chain(), f"audit_chain failed on node "
               f"{node.node_id}")
    _say("jash", ok=True, height=net.heights[0],
         classic_args=2 ** sizes.classic_arg_bits)


def phase_sha256(sizes: Sizes, clock: _CompileClock) -> None:
    rng = np.random.default_rng(0)
    headers = rng.integers(0, 2 ** 32, size=(sizes.n_headers, 20),
                           dtype=np.uint32)            # 80-byte headers
    c0 = clock.total
    got = np.asarray(sha256_words(jnp.asarray(headers), backend="pallas",
                                  interpret=sizes.interpret))
    jnp_path = np.asarray(sha256_words(jnp.asarray(headers), backend="jnp"))
    want = ref.sha256_words_hashlib(headers)
    _say("sha256", n=sizes.n_headers, interpret=sizes.interpret,
         compile_s=f"{clock.total - c0:.3f}",
         eq_jnp=bool((got == jnp_path).all()),
         eq_hashlib=bool((got == want).all()))
    _check((got == jnp_path).all(), "Pallas SHA-256 differs from jnp path")
    _check((got == want).all(), "Pallas SHA-256 differs from hashlib")


def _model_cfg(sizes: Sizes):
    cfg = get_config(sizes.model) if isinstance(sizes.model, str) \
        else sizes.model
    if sizes.n_layers is not None and sizes.n_layers != cfg.n_layers:
        _say("model", depth_cut=f"{cfg.n_layers}->{sizes.n_layers} layers")
        cfg = dataclasses.replace(cfg, n_layers=sizes.n_layers)
    return cfg


def _model_wl(sizes: Sizes, cfg, mesh=None) -> ModelTrainingWorkload:
    return ModelTrainingWorkload(cfg=cfg, seq_len=sizes.seq_len,
                                 batch=sizes.batch,
                                 block_microsteps=sizes.microsteps,
                                 mesh=mesh)


class _Mined(NamedTuple):
    store: ChainStore
    digest: str
    hashes: list
    book: list
    losses: list
    params: Any            # host copy of the tip params, when asked for


def _mine_model_chain(sizes: Sizes, clock: _CompileClock, cfg, tag: str,
                      mesh=None, keep_params: bool = False) -> _Mined:
    """Mine ``n_model_blocks`` blocks into a fresh in-memory journal.
    Nothing of the miner outlives the call but the journal and what
    ``_Mined`` copies out, so the device holds one train state at a
    time."""
    store = ChainStore()
    wl = _model_wl(sizes, cfg, mesh)
    miner = Node(node_id=0, workloads={"model_train": wl}, store=store)
    losses = []
    for i in range(sizes.n_model_blocks):
        c0, t0 = clock.total, time.perf_counter()
        r = miner.mine_block("model_train")
        dt = time.perf_counter() - t0
        losses.append(r.payload.loss)
        _say(tag, block=i, loss=repr(r.payload.loss), wall_s=f"{dt:.3f}",
             compile_s=f"{clock.total - c0:.3f}")
        _check(math.isfinite(r.payload.loss), f"block {i} loss not finite")
    out = _Mined(store, wl.state_digest(),
                 [b.block_hash for b in miner.ledger.blocks],
                 sorted(miner.book.balances.items()), losses,
                 jax.device_get(wl.snapshot()[1].params) if keep_params
                 else None)
    _say(tag, tip_digest=out.digest[:16], peak_bytes_in_use=_peak_bytes())
    del miner, wl, r
    gc.collect()
    return out


def _replay(sizes: Sizes, clock: _CompileClock, cfg, mined: _Mined,
            tag: str, mesh=None) -> None:
    """``Node.recover`` the journal on a fresh node (on ``mesh``, or on
    one chip) and require the miner's digest, hashes and book."""
    fresh = Node(node_id=0,
                 workloads={"model_train": _model_wl(sizes, cfg, mesh)})
    c0, t0 = clock.total, time.perf_counter()
    node = Node.recover(mined.store, node=fresh)
    dt = time.perf_counter() - t0
    adopted = node.last_recovery.adopted_height
    digest = node.workloads["model_train"].state_digest()
    hashes = [b.block_hash for b in node.ledger.blocks]
    book = sorted(node.book.balances.items())
    del node, fresh                  # free the state before any failure
    gc.collect()
    _say(tag, replay="one chip" if mesh is None else dict(mesh.shape),
         adopted=adopted, wall_s=f"{dt:.3f}",
         compile_s=f"{clock.total - c0:.3f}", digest_eq=digest ==
         mined.digest, peak_bytes_in_use=_peak_bytes())
    _check(adopted == sizes.n_model_blocks,
           f"replay adopted {adopted} of {sizes.n_model_blocks} blocks")
    _check(digest == mined.digest, "replayed params digest differs")
    _check(hashes == mined.hashes, "replayed block hashes differ")
    _check(book == mined.book, "replayed credit book differs")


def phase_model(sizes: Sizes, clock: _CompileClock) -> None:
    cfg = _model_cfg(sizes)
    _say("model", cfg=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
         heads=f"{cfg.n_heads}/{cfg.n_kv_heads}",
         head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
         vocab=cfg.vocab_size, seq_len=sizes.seq_len, batch=sizes.batch,
         microsteps=sizes.microsteps)
    _replay(sizes, clock, cfg, _mine_model_chain(sizes, clock, cfg, "model"),
            "model")


def mesh_jash(sizes: Sizes, clock: _CompileClock, mesh) -> None:
    """Full and optimal blocks mined over the mesh's miner axis
    (``shard_map``) must verify on a plain one-device node."""
    miner = Node(node_id=0, mesh=mesh,
                 classic_arg_bits=sizes.classic_arg_bits)
    plain = Node(node_id=1, classic_arg_bits=sizes.classic_arg_bits)
    for wl in ("full", "optimal"):
        miner.submit(_collatz(sizes.jash_arg_bits))
        c0, t0 = clock.total, time.perf_counter()
        r = miner.mine_block(wl)
        dt = time.perf_counter() - t0
        ok = plain.receive(r.record.to_block(), r.payload, origin=0)
        _say("mesh", jash=wl, mesh=dict(mesh.shape), wall_s=f"{dt:.3f}",
             compile_s=f"{clock.total - c0:.3f}", plain_accepts=ok)
        _check(ok, f"plain node rejected the mesh-mined {wl} block")
    _check(plain.audit_chain(), "audit_chain failed on the plain node")


def _where_they_part(sizes: Sizes, clock: _CompileClock, cfg,
                     mined: _Mined) -> None:
    """After a failed cross-mesh replay: train the same blocks on one
    chip and print how far the two runs are apart."""
    plain = _mine_model_chain(sizes, clock, cfg, "one-chip",
                              keep_params=True)
    n_diff, n_all, max_abs = 0, 0, 0.0
    for a, b in zip(jax.tree.leaves(mined.params),
                    jax.tree.leaves(plain.params)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        n_diff += int((a != b).sum())
        n_all += a.size
        max_abs = max(max_abs, float(np.abs(a - b).max()))
    _say("mesh-model", losses_mesh=mined.losses, losses_one_chip=plain.losses,
         params_differing=f"{n_diff}/{n_all}", max_abs_diff=max_abs)


def phase_mesh(sizes: Sizes, clock: _CompileClock, chips: int) -> None:
    mesh = make_host_mesh()
    _check(mesh.devices.size == chips,
           f"mesh holds {mesh.devices.size} devices, need {chips}")
    mesh_jash(sizes, clock, mesh)
    # FSDP training over the mesh, replayed on one chip
    cfg = _model_cfg(sizes)
    mined = _mine_model_chain(sizes, clock, cfg, "mesh-model", mesh=mesh,
                              keep_params=True)
    try:
        _replay(sizes, clock, cfg, mined, "mesh-model")
    except SmokeFailure:
        _where_they_part(sizes, clock, cfg, mined)
        raise


def run(sizes: Sizes, chips: int = 1) -> dict:
    """Every phase of the run, in order; raises on the first failure and
    returns the device line's ``device`` object."""
    clock = _CompileClock()
    dev = phase_device(sizes, chips)
    if chips > 1:
        phase_mesh(sizes, clock, chips)
    else:
        phase_jash(sizes, clock)
        phase_sha256(sizes, clock)
        phase_model(sizes, clock)
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh phase instead of phases 1-4's "
                         "one-chip path (default 1)")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    print(f"compile cache: {cache}", flush=True)
    t0 = time.perf_counter()
    try:
        dev = run(Sizes(), chips=args.chips)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
