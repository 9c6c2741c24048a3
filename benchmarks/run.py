"""PNPCoin benchmark harness.

The paper has no result tables (position paper) — each benchmark pins one
of its quantitative *claims* instead:

  hash_flops      §1 fn.1  "20 FLOPS per hash" -> measured FLOP/hash of our
                           SHA-256 + the implied network-FLOPS arithmetic
  network_claim   §1       34 EH/s x FLOP/hash vs 200 PFLOP/s Summit
  block_turnaround §3      "computed ... for a turnaround of minutes"
  mode_overhead   §3.3     full vs optimal aggregation cost
  pouw_overhead   §1/§5    training-as-mining vs plain training loop
                           (the paper's implicit baseline)
  docking         §4       use-case throughput (pairs/s)
  verification    §3/DESIGN quorum re-execution cost vs fraction
  roofline        (e)/(g)  dry-run roofline table from experiments/dryrun
  merkle_commit   DESIGN §6 device block commitment vs the seed Python path
  executor_chunked DESIGN §6 chunked fused full-mode dispatch
  block_scan      DESIGN §6 scan-fused PoUW block vs per-microstep dispatch
  sim_gossip      DESIGN §9 async gossip sim: fork depth, orphan rate,
                  time-to-finality under partitions and adversaries
                  (consumes the SimReport of the canonical scenarios),
                  plus the DESIGN §10 scale scenarios (16x128, 64x512)
                  the shared verify cache makes tractable
  verify_pipeline DESIGN §10 ``verify_chain_batched`` over a mixed
                  256-block segment vs the per-block receive-path loop
  workload_suite  DESIGN §11 application workloads (SAT / GAN inversion /
                  docking): mine + verify throughput per family, and the
                  SAT certificate-check vs re-mine asymmetry

Prints ``name,us_per_call,derived`` CSV rows.  The pipeline rows are
also written machine-readably to BENCH_pipeline.json (repo root): the
latest run's rows sit at the top level and every full run appends a
``history`` entry (git sha, date, rows), so the perf trajectory across
PRs stays recorded.  ``--smoke`` runs a reduced subset (CI) and *gates*:
the reduced ``merkle_commit`` and ``verify_chain_batched`` timings are
compared against the committed ``smoke_baseline`` and the run fails on a
>2.5x slowdown (generous tolerance for CI jitter).
"""
from __future__ import annotations

import glob
import json
import os
import statistics
import subprocess
import time

import jax
import jax.numpy as jnp
import numpy as np

ROWS = []
BENCH_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          os.pardir, "BENCH_pipeline.json")

# --smoke fails when a gated metric is slower than the committed
# smoke_baseline by more than this factor (CI-jitter tolerance)
SMOKE_SLOWDOWN_LIMIT = 2.5


_QUIET = False     # True while the full run re-measures at smoke scale


def row(name: str, us_per_call: float, derived: str = "") -> None:
    if _QUIET:
        # the full run's smoke-baseline pass re-runs sections at
        # reduced scale; emitting their rows would duplicate names
        # (e.g. merkle_commit.device) with conflicting timings
        return
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.3f},{derived}", flush=True)


def _timeit(fn, *args, n: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6       # us


# ---------------------------------------------------------------------------


def bench_hash_flops():
    """§1 footnote: 'we consider 20 FLOPS per hash, but this can be 20000
    on a modern CPU'."""
    from repro.kernels.ops import sha256_words
    msg = jnp.zeros((4096, 20), jnp.uint32)           # 80-byte headers
    lowered = jax.jit(lambda m: sha256_words(m)).lower(msg)
    cost = lowered.cost_analysis()
    flops_per_hash = float(cost.get("flops", 0.0)) / msg.shape[0]
    us = _timeit(jax.jit(lambda m: sha256_words(m)), msg)
    hashes_per_s = msg.shape[0] / (us * 1e-6)
    row("hash_flops.flop_per_hash", us / msg.shape[0],
        f"flops_per_hash={flops_per_hash:.0f} (paper assumes 20..20000)")
    row("hash_flops.throughput", us,
        f"hashes_per_s={hashes_per_s:.3g} (1 CPU miner)")
    return flops_per_hash


def bench_network_claim(flops_per_hash: float):
    """§1: 34e18 hash/s * FLOP/hash vs Summit 200 PFLOP/s = 'four orders
    of magnitude' / '50000 supercomputers'."""
    network_hs = 34e18
    summit = 200e15
    for label, fph in [("paper_20", 20.0), ("measured", flops_per_hash)]:
        implied = network_hs * fph
        ratio = implied / summit
        row(f"network_claim.{label}", 0.0,
            f"implied_flops={implied:.3g} summit_ratio={ratio:.3g}")


def bench_block_turnaround():
    """§3: block turnaround for three payload kinds on this 1-CPU miner."""
    import dataclasses
    from repro.configs import get_config, reduced
    from repro.configs.base import InputShape
    from repro.core.authority import classic_jash
    from repro.core.executor import run_full
    from repro.core.jash import Jash, JashMeta, collatz_jash
    from repro.core.pow_train import PoUWTrainer
    from repro.train.steps import TrainHparams

    # classic (sha256) block over 2^12 args
    t0 = time.perf_counter()
    run_full(Jash("c", classic_jash().fn, JashMeta(arg_bits=12, res_bits=256),
                  example_args=(jnp.uint32(0),)))
    row("block_turnaround.classic_4096args",
        (time.perf_counter() - t0) * 1e6, "full sha256 block")

    # collatz block
    j = collatz_jash(max_steps=512)
    j2 = Jash(j.name, j.fn, JashMeta(arg_bits=12, res_bits=32),
              example_args=j.example_args)
    t0 = time.perf_counter()
    run_full(j2)
    row("block_turnaround.collatz_4096args",
        (time.perf_counter() - t0) * 1e6, "bounded-while block")

    # training block
    cfg = reduced(get_config("qwen3-0.6b"))
    tr = PoUWTrainer(cfg, InputShape("t", 64, 8, "train"),
                     hp=TrainHparams(), mode="full", n_miners=4)
    tr.run_block()                                    # compile
    t0 = time.perf_counter()
    tr.run_block()
    row("block_turnaround.train_block",
        (time.perf_counter() - t0) * 1e6, "PoUW train step + ledger")


def bench_mode_overhead():
    from repro.core.executor import run_full, run_optimal
    from repro.core.jash import Jash, JashMeta

    def fn(a):
        return (a * jnp.uint32(2654435761)) ^ jnp.uint32(0xDEADBEEF)

    j = Jash("mix", fn, JashMeta(arg_bits=14, res_bits=32),
             example_args=(jnp.uint32(0),))
    t0 = time.perf_counter()
    run_full(j)
    t_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_optimal(j)
    t_opt = time.perf_counter() - t0
    row("mode_overhead.full_16k", t_full * 1e6, "all results + hashes")
    row("mode_overhead.optimal_16k", t_opt * 1e6,
        f"argmin only; full/optimal={t_full / max(t_opt, 1e-9):.2f}x")


def bench_pouw_overhead():
    """Training-as-mining vs plain training: ledger/merkle/reward cost."""
    from repro.configs import get_config, reduced
    from repro.configs.base import InputShape
    from repro.core.pow_train import PoUWTrainer
    from repro.data.pipeline import SyntheticTokenPipeline
    from repro.train.steps import (TrainHparams, make_train_state,
                                   make_train_step)

    cfg = reduced(get_config("qwen3-0.6b"))
    shape = InputShape("t", 64, 8, "train")
    hp = TrainHparams()
    n = 5

    # plain baseline
    pipe = SyntheticTokenPipeline(cfg, shape, seed=0)
    state = make_train_state(cfg, jax.random.key(0))
    step = jax.jit(make_train_step(cfg, hp))
    state, _ = step(state, pipe.batch(0))             # compile
    t0 = time.perf_counter()
    for i in range(n):
        state, m = step(state, pipe.batch(i + 1))
    jax.block_until_ready(m["loss"])
    t_plain = (time.perf_counter() - t0) / n

    # PoUW chain
    tr = PoUWTrainer(cfg, shape, hp=hp, mode="full", n_miners=4)
    tr.run_block()
    t0 = time.perf_counter()
    tr.run(n)
    t_pouw = (time.perf_counter() - t0) / n

    tokens = shape.global_batch * shape.seq_len
    row("pouw_overhead.plain_step", t_plain * 1e6,
        f"tokens_per_s={tokens / t_plain:.0f}")
    row("pouw_overhead.pouw_block", t_pouw * 1e6,
        f"tokens_per_s={tokens / t_pouw:.0f} "
        f"overhead={(t_pouw / t_plain - 1) * 100:.1f}%")


def bench_docking():
    """§4 use case: pairs/s through the full-mode pipeline."""
    from repro.core.executor import run_full
    from repro.core.jash import Jash, JashMeta

    N_R, N_P = 64, 64

    def matcher(b):
        r, p = b % jnp.uint32(N_R), b // jnp.uint32(N_R)
        score = (r * jnp.uint32(2654435761) ^ p * jnp.uint32(40503)) \
            % jnp.uint32(1000)
        return jnp.where(score < 200, jnp.uint32(1), jnp.uint32(0))

    j = Jash("dock", matcher,
             JashMeta(arg_bits=12, res_bits=2, max_arg=N_R * N_P),
             example_args=(jnp.uint32(0),))
    t0 = time.perf_counter()
    fr = run_full(j)
    dt = time.perf_counter() - t0
    binds = int((fr.results[:, 0] == 1).sum())
    row("docking.full_4096_pairs", dt * 1e6,
        f"pairs_per_s={N_R * N_P / dt:.0f} binds={binds}")


def bench_verification():
    from repro.core.executor import run_full
    from repro.core.jash import Jash, JashMeta
    from repro.core.verify import quorum_verify

    def fn(a):
        return a * jnp.uint32(2654435761)

    j = Jash("v", fn, JashMeta(arg_bits=12, res_bits=32),
             example_args=(jnp.uint32(0),))
    t0 = time.perf_counter()
    fr = run_full(j)
    t_mine = time.perf_counter() - t0
    for frac in (0.05, 0.25):
        t0 = time.perf_counter()
        rep = quorum_verify(j, fr, fraction=frac)
        dt = time.perf_counter() - t0
        row(f"verification.frac_{frac}", dt * 1e6,
            f"checked={rep.n_checked} verify/mine={dt / max(t_mine, 1e-9):.3f}")


def _median_ms(fn, n: int) -> float:
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def bench_commit_pipeline(n_leaves: int = 4096,
                          train_section: bool = True) -> dict:
    """DESIGN.md §6: the on-device block-commitment pipeline vs the seed.

    merkle_commit compares the seed's end-to-end commit path from a mined
    FullResult — the per-arg Python loop building leaf bytes plus the
    Python/hashlib ``merkle_root`` (exactly the code the pipeline
    replaced) — against ``FullResult.commit_root()``, the fused device
    tree over the leaf digests the executor already computed in-dispatch.
    The hashlib-root-only baseline (no leaf building) is recorded too.
    """
    from repro.core.executor import run_full
    from repro.core.jash import Jash, JashMeta
    from repro.core.ledger import merkle_root
    from repro.core.pow_train import PoUWTrainer
    from repro.configs import get_config, reduced
    from repro.configs.base import InputShape
    from repro.train.steps import TrainHparams

    arg_bits = int(np.log2(n_leaves))
    assert 1 << arg_bits == n_leaves

    def mixer(a):
        h = a * jnp.uint32(2654435761)
        return jnp.stack(
            [(h ^ jnp.uint32((0x9E3779B9 * (i + 1)) & 0xFFFFFFFF)) *
             jnp.uint32(2246822519) for i in range(8)])

    j = Jash("commit-bench", mixer,
             JashMeta(arg_bits=arg_bits, res_bits=256),
             example_args=(jnp.uint32(0),))

    # --- executor_chunked: the fused full-mode dispatch ------------------
    run_full(j)                                        # compile
    us_full = _median_ms(lambda: run_full(j), 5) * 1e3
    run_full(j, chunk_size=n_leaves // 4)              # compile (same shape?)
    us_chunk = _median_ms(lambda: run_full(j, chunk_size=n_leaves // 4),
                          5) * 1e3
    row("executor_chunked.one_dispatch", us_full,
        f"args_per_s={n_leaves / (us_full * 1e-6):.3g}")
    row("executor_chunked.four_chunks", us_chunk,
        f"args_per_s={n_leaves / (us_chunk * 1e-6):.3g} bit-identical")

    # --- merkle_commit ---------------------------------------------------
    fr = run_full(j)

    def seed_commit():
        # the seed's commit path, verbatim: per-i leaf bytes + hashlib tree
        leaves = tuple(fr.args[i].tobytes() + fr.results[i].tobytes()
                       for i in range(len(fr.args)))
        return merkle_root(leaves, backend="hashlib")

    leaves_prebuilt = fr.merkle_leaves
    fr.commit_root()                                   # compile device tree
    assert fr.commit_root() == seed_commit()           # bit-identical
    ms_seed = _median_ms(seed_commit, 7)
    ms_root_only = _median_ms(
        lambda: merkle_root(leaves_prebuilt, backend="hashlib"), 7)
    ms_dev = _median_ms(fr.commit_root, 15)
    speedup = ms_seed / ms_dev
    row("merkle_commit.seed_path", ms_seed * 1e3,
        "python leaf build + hashlib merkle_root (seed code)")
    row("merkle_commit.hashlib_root_only", ms_root_only * 1e3,
        "hashlib merkle_root on prebuilt leaves")
    row("merkle_commit.device", ms_dev * 1e3,
        f"speedup={speedup:.2f}x vs seed path "
        f"({ms_root_only / ms_dev:.2f}x vs root-only)")

    # --- block_scan: scan-fused PoUW block -------------------------------
    if not train_section:
        # reduced-scale re-measure for the smoke gate: only the merkle
        # metric is consumed, skip the (expensive) trainer section
        return {
            "n_leaves": n_leaves,
            "merkle_commit": {
                "us_seed_path": ms_seed * 1e3,
                "us_hashlib_root_only": ms_root_only * 1e3,
                "us_device": ms_dev * 1e3,
                "speedup": speedup,
            },
        }
    cfg = reduced(get_config("qwen3-0.6b"))
    shape = InputShape("t", 32, 4, "train")
    micro = 4
    tr = PoUWTrainer(cfg, shape, hp=TrainHparams(), mode="full",
                     n_miners=4, block_microsteps=micro)
    tr.run_block()                                     # compile scan block
    ms_scan = _median_ms(tr.run_block, 3)

    state, batch = tr.state, tr.pipeline.batch(0)
    tr._train_step(state, batch)                       # compile single step

    def seed_microsteps():
        s = state
        for _ in range(micro):
            s, m = tr._train_step(s, batch)
        jax.block_until_ready(m["loss"])

    ms_seed_steps = _median_ms(seed_microsteps, 3)
    row("block_scan.scan_block", ms_scan * 1e3,
        f"{micro} microsteps, one dispatch + ledger")
    row("block_scan.per_step_dispatch", ms_seed_steps * 1e3,
        f"seed pattern: {micro} dispatches, no ledger; "
        f"scan/step={ms_scan / ms_seed_steps:.2f}")

    return {
        "n_leaves": n_leaves,
        "merkle_commit": {
            "us_seed_path": ms_seed * 1e3,
            "us_hashlib_root_only": ms_root_only * 1e3,
            "us_device": ms_dev * 1e3,
            "speedup": speedup,
            "speedup_vs_root_only": ms_root_only / ms_dev,
            "baseline": "seed commit path: per-arg Python leaf build + "
                        "hashlib merkle_root, as in the seed executor",
        },
        "executor_chunked": {
            "us_one_dispatch": us_full,
            "us_four_chunks": us_chunk,
            "args_per_s": n_leaves / (us_full * 1e-6),
        },
        "block_scan": {
            "block_microsteps": micro,
            "us_scan_block": ms_scan * 1e3,
            "us_per_step_dispatch": ms_seed_steps * 1e3,
        },
    }


def bench_verify_pipeline(n_blocks: int = 256, full_arg_bits: int = 10
                          ) -> dict:
    """DESIGN §10: batched chain re-verification vs the per-block
    receive path.

    The segment mirrors what fork choice and chain sync actually
    replay — a mixed chain, half full-mode blocks drawn from
    ``n_publications`` distinct publications each re-mined repeatedly
    (deterministic mining makes the repeats byte-identical evidence,
    exactly as real classic/re-mined chains do, but every block is its
    own payload/evidence object — nothing is shared by identity), and
    half classic blocks.  The per-block baseline is exactly the
    ``wl.verify`` loop ``consider_chain`` used to run (hashlib root +
    quorum dispatch per full block); ``verify_chain_batched`` groups
    the segment per workload: full blocks dedup byte-identical
    evidence and share one stacked leaf-digest dispatch, one forest
    reduction and one stacked quorum dispatch per publication, classic
    blocks share a single replay of their common arg space."""
    import dataclasses as _dc

    from repro.core.executor import run_full
    from repro.core.jash import Jash, JashMeta
    from repro.chain.workload import (
        BlockContext, BlockPayload, ClassicSha256Workload,
        JashFullWorkload, verify_chain_batched)

    n_publications = 8

    def make_jash(salt):
        def mixer(a):
            h = (a + jnp.uint32(salt)) * jnp.uint32(2654435761)
            return jnp.stack(
                [(h ^ jnp.uint32((0x9E3779B9 * (i + 1)) & 0xFFFFFFFF)) *
                 jnp.uint32(2246822519) for i in range(8)])
        return Jash(f"verify-bench-{salt}", mixer,
                    JashMeta(arg_bits=full_arg_bits, res_bits=256),
                    example_args=(jnp.uint32(0),))

    pubs = [make_jash(s) for s in range(n_publications)]
    fulls = [run_full(j) for j in pubs]
    workloads = {"full": JashFullWorkload(),
                 "classic": ClassicSha256Workload(arg_bits=full_arg_bits)}
    cw = workloads["classic"]

    def full_payload(slot):
        j, fr = pubs[slot % n_publications], fulls[slot % n_publications]
        # fresh arrays + payload per block: byte-identical to the
        # publication's evidence (deterministic re-mine), distinct
        # objects (dedup must work by content, not identity)
        fr = _dc.replace(fr, args=fr.args.copy(),
                         results=fr.results.copy())
        return BlockPayload(
            workload="full", jash_id=j.source_id(),
            merkle_root=fr.commit_root(), n_results=len(fr.args),
            jash=j, full=fr)

    payloads = [full_payload(i // 2) if i % 2 == 0
                else cw.mine(cw.prepare(BlockContext(height=i,
                                                     prev_hash="")))
                for i in range(n_blocks)]

    # explicit raises, not asserts: these checks are the timed work —
    # under ``python -O`` an assert would strip and time empty bodies
    def per_block():
        if not all(workloads[p.workload].verify(p) for p in payloads):
            raise RuntimeError("per-block verification rejected a block")

    def batched():
        if not verify_chain_batched(workloads, payloads):
            raise RuntimeError("batched verification rejected the segment")

    batched()                                          # compile
    per_block()
    ms_loop = _median_ms(per_block, 3)
    ms_batch = _median_ms(batched, 3)
    speedup = ms_loop / ms_batch
    row(f"verify_pipeline.per_block_{n_blocks}", ms_loop * 1e3,
        f"receive-path wl.verify loop (half full over {n_publications} "
        "publications, half classic)")
    row(f"verify_pipeline.batched_{n_blocks}", ms_batch * 1e3,
        f"verify_chain_batched speedup={speedup:.2f}x")
    return {
        "n_blocks": n_blocks,
        "full_arg_bits": full_arg_bits,
        "composition": (f"alternating full / classic; full blocks from "
                        f"{n_publications} publications (byte-identical "
                        "re-mines, distinct objects)"),
        "us_per_block_loop": ms_loop * 1e3,
        "us_batched": ms_batch * 1e3,
        "speedup": speedup,
    }


def bench_sim_scale() -> dict:
    """DESIGN §10: the gossip scale scenarios the verify cache + batched
    fork choice make tractable.  Wall-clock covers mining AND the N-1
    per-block re-verifications (cached: once per trust domain)."""
    from repro.chain.sim import throughput_scenario

    out = {}
    for name, nodes, blocks in (("gossip_16x128", 16, 128),
                                ("gossip_64x512", 64, 512)):
        sim = throughput_scenario(nodes, blocks)
        t0 = time.perf_counter()
        rep = sim.run()
        dt = time.perf_counter() - t0
        if not rep.converged or rep.credit_divergence != 0.0:
            raise RuntimeError(
                f"{name}: scenario diverged (converged={rep.converged}, "
                f"divergence={rep.credit_divergence})")
        hits = sim.verify_cache.hits if sim.verify_cache else 0
        row(f"sim_gossip.{name}", dt * 1e6,
            f"events={rep.n_events} events_per_s={rep.n_events / dt:.0f} "
            f"mined={rep.blocks_mined} cache_hits={hits} "
            f"converged={rep.converged}")
        out[name] = {"wall_s": dt, "events": rep.n_events,
                     "blocks_mined": rep.blocks_mined,
                     "verify_cache_hits": hits}
    return out


def bench_workload_suite(*, sat_vars: int = 12, sat_clauses: int = 48,
                         grid_bits: int = 10, dock: int = 32,
                         gan_rounds: int = 3, segment: int = 8) -> dict:
    """DESIGN §11: mine/verify throughput per application workload
    family, and the SAT certificate-check vs re-mine asymmetry.

    Each family is timed from both chairs: the miner's
    ``mine(prepare(ctx))`` and a *separate* verifier instance's
    ``verify`` (what every peer pays on receive).  The headline number
    is ``sat_cert_verify``: checking a committed satisfiability
    certificate is O(clauses) host work, orders of magnitude under the
    full-space re-mine — the first mine-hard/verify-cheap asymmetry in
    the repo.  GAN rounds re-jit per round (each round's grid is a new
    closure), so their cost is end-to-end including compile — that is
    what a real node pays.  Docking also times ``verify_batch`` over a
    repeated-screening segment (content dedup collapses it to ~one
    verification)."""
    from repro.chain.workload import BlockContext
    from repro.chain.workloads import (DockingWorkload,
                                       GanInversionWorkload, SatWorkload)

    def ctx(h: int) -> BlockContext:
        return BlockContext(height=h, prev_hash="")

    out: dict = {}

    # --- SAT: certificate asymmetry ----------------------------------
    miner = SatWorkload(n_vars=sat_vars, n_clauses=sat_clauses, seed=1)
    verifier = SatWorkload(n_vars=sat_vars, n_clauses=sat_clauses, seed=1)
    sat_h = unsat_h = sat_p = unsat_p = None
    for h in range(64):
        p = miner.mine(miner.prepare(ctx(h)))
        if p.certificate is not None and sat_p is None:
            sat_h, sat_p = h, p
        if p.certificate is None and unsat_p is None:
            unsat_h, unsat_p = h, p
        if sat_p is not None and unsat_p is not None:
            break
    if sat_p is None or unsat_p is None:
        raise RuntimeError("no SAT+UNSAT pair in 64 instances — "
                           "adjust sat_vars/sat_clauses")
    ms_mine = _median_ms(lambda: miner.mine(miner.prepare(ctx(sat_h))), 5)
    for p, name in ((sat_p, "cert"), (unsat_p, "refute")):
        if not verifier.verify(p):
            raise RuntimeError(f"sat {name} verification rejected an "
                               "honest block")
    ms_cert = _median_ms(lambda: verifier.verify(sat_p), 20)
    ms_refute = _median_ms(lambda: verifier.verify(unsat_p), 5)
    n_args = 1 << sat_vars
    cert_speedup = ms_mine / max(ms_cert, 1e-9)
    row("workload_suite.sat_mine", ms_mine * 1e3,
        f"2^{sat_vars} assignments, args_per_s="
        f"{n_args / (ms_mine * 1e-3):.3g}")
    row("workload_suite.sat_cert_verify", ms_cert * 1e3,
        f"O({sat_clauses} clauses) witness check; "
        f"cert_vs_remine={cert_speedup:.0f}x")
    row("workload_suite.sat_refute_verify", ms_refute * 1e3,
        f"hashlib root + quorum over the table; "
        f"vs_mine={ms_mine / max(ms_refute, 1e-9):.2f}x")
    out["sat"] = {"n_vars": sat_vars, "us_mine": ms_mine * 1e3,
                  "us_cert_verify": ms_cert * 1e3,
                  "us_refute_verify": ms_refute * 1e3,
                  "cert_vs_remine_speedup": cert_speedup}

    # --- GAN inversion: stateful rounds ------------------------------
    gm = GanInversionWorkload(seed=0, grid_bits=grid_bits)
    gv = GanInversionWorkload(seed=0, grid_bits=grid_bits)
    mine_ms, verify_ms = [], []
    for r in range(gan_rounds):
        t0 = time.perf_counter()
        p = gm.mine(gm.prepare(ctx(r)))
        mine_ms.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        if not gv.verify(p):
            raise RuntimeError("gan round verification rejected an "
                               "honest block")
        verify_ms.append((time.perf_counter() - t0) * 1e3)
    ms_gmine = statistics.median(mine_ms)
    ms_gverify = statistics.median(verify_ms)
    row("workload_suite.gan_mine", ms_gmine * 1e3,
        f"2^{grid_bits} latents/round incl. per-round jit, err -> "
        f"{gm.inversion_error():.4f}")
    row("workload_suite.gan_verify", ms_gverify * 1e3,
        "stateful replay + zoom-digest compare (doubles as state sync)")
    out["gan"] = {"grid_bits": grid_bits, "rounds": gan_rounds,
                  "us_mine": ms_gmine * 1e3,
                  "us_verify": ms_gverify * 1e3}

    # --- docking: consensus-bound data bundle ------------------------
    dm = DockingWorkload(n_r=dock, n_p=dock, seed=0)
    dv = DockingWorkload(n_r=dock, n_p=dock, seed=0)
    dm.mine(dm.prepare(ctx(0)))                       # compile
    ms_dmine = _median_ms(lambda: dm.mine(dm.prepare(ctx(0))), 5)
    dp = dm.mine(dm.prepare(ctx(0)))
    if not dv.verify(dp):
        raise RuntimeError("docking verification rejected an honest block")
    ms_dverify = _median_ms(lambda: dv.verify(dp), 5)
    seg = [dm.mine(dm.prepare(ctx(h))) for h in range(segment)]
    if not all(dv.verify_batch(seg)):
        raise RuntimeError("docking batched verification rejected the "
                           "segment")
    ms_dbatch = _median_ms(lambda: dv.verify_batch(seg), 5)
    pairs = dock * dock
    row("workload_suite.dock_mine", ms_dmine * 1e3,
        f"pairs_per_s={pairs / (ms_dmine * 1e-3):.0f}")
    row("workload_suite.dock_verify", ms_dverify * 1e3,
        "bundle-checksum bind + hashlib root + quorum")
    row(f"workload_suite.dock_verify_batch_{segment}", ms_dbatch * 1e3,
        f"content dedup: {segment} repeat screenings ~ "
        f"{ms_dbatch / max(ms_dverify, 1e-9):.2f}x one verify")
    out["docking"] = {"n_pairs": pairs, "us_mine": ms_dmine * 1e3,
                      "us_verify": ms_dverify * 1e3, "segment": segment,
                      "us_verify_batch": ms_dbatch * 1e3}
    return out


def bench_sim_gossip(n_lanes: int = 1):
    """DESIGN §9: the async gossip simulator under partition + adversary
    scenarios.  Each row consumes the deterministic ``SimReport`` — fork
    depth histogram, orphan rate, time-to-finality — plus the wallclock
    cost of driving the scenario (events/s is the simulator's own
    overhead figure; block *mining* dominates it)."""
    from repro.chain.sim import adversarial_scenario, partitioned_scenario

    for name, build in (
        ("partition_4node",
         lambda: partitioned_scenario(n_nodes=4, seed=0,
                                      n_lanes=n_lanes)),
        ("adversarial_5node",
         lambda: adversarial_scenario(n_honest=3, seed=0)),
    ):
        sim = build()
        t0 = time.perf_counter()
        rep = sim.run()
        dt = time.perf_counter() - t0
        if not rep.converged or rep.credit_divergence != 0.0:
            raise RuntimeError(
                f"{name}: scenario diverged (converged={rep.converged}, "
                f"divergence={rep.credit_divergence})")
        depths = ";".join(f"d{k}x{v}"
                          for k, v in rep.fork_depth_hist.items())
        row(f"sim_gossip.{name}", dt * 1e6,
            f"events={rep.n_events} events_per_s={rep.n_events / dt:.0f} "
            f"mined={rep.blocks_mined} orphan_rate={rep.orphan_rate:.2f} "
            f"forks=[{depths}] ttf_mean_s={rep.ttf_mean:.2f} "
            f"ttf_max_s={rep.ttf_max:.2f}")


def bench_recovery(n_blocks: int = 512, arg_bits: int = 6) -> dict:
    """DESIGN §12: journal replay throughput — what a restart costs.
    Mines a classic chain into an in-memory journal, then times
    ``Node.recover`` replaying it through the batched verify path."""
    from repro.chain import ChainStore, Node

    donor = Node(node_id=0, classic_arg_bits=arg_bits, store=ChainStore())
    for _ in range(n_blocks):
        donor.mine_block()
    data = donor.store.to_bytes()
    t0 = time.perf_counter()
    node = Node.recover(ChainStore.from_bytes(data),
                        node=Node(node_id=0, classic_arg_bits=arg_bits))
    dt = time.perf_counter() - t0
    if node.ledger.tip_hash != donor.ledger.tip_hash:
        raise RuntimeError("recovery replay diverged from the donor tip")
    row(f"recovery.replay_{n_blocks}", dt * 1e6,
        f"blocks_per_s={n_blocks / dt:.0f} journal_bytes={len(data)}")
    return {"n_blocks": n_blocks, "wall_s": dt,
            "blocks_per_s": n_blocks / dt, "journal_bytes": len(data)}


def bench_chaos(n_nodes: int = 16, n_blocks: int = 24) -> dict:
    """DESIGN §12: the crash/corrupt/long-range-rewrite chaos scenario —
    wallclock for the full fault gauntlet plus its recovery/finality
    counters (any divergence is a hard failure, not a slow row)."""
    from repro.chain.sim import chaos_scenario

    sim = chaos_scenario(n_nodes=n_nodes, n_blocks=n_blocks)
    t0 = time.perf_counter()
    rep = sim.run()
    dt = time.perf_counter() - t0
    if (not rep.converged or rep.credit_divergence != 0.0
            or rep.finalized_divergence != 0):
        raise RuntimeError(
            f"chaos_scenario diverged (converged={rep.converged}, "
            f"finalized_divergence={rep.finalized_divergence})")
    row(f"sim_chaos.{n_nodes}x{n_blocks}", dt * 1e6,
        f"events={rep.n_events} events_per_s={rep.n_events / dt:.0f} "
        f"recoveries={rep.recoveries} truncated={rep.truncated_records} "
        f"finality_rejects={rep.finality_rejects} "
        f"converged={rep.converged}")
    return {"n_nodes": n_nodes, "blocks": n_blocks, "wall_s": dt,
            "events": rep.n_events, "recoveries": rep.recoveries,
            "truncated_records": rep.truncated_records,
            "finality_rejects": rep.finality_rejects}


def bench_model_pouw(n_blocks: int = 4) -> dict:
    """DESIGN §16: real-model PoUW on the CI micro transformer —
    blocks/s mined (steady state, after the one shared XLA compile),
    the verifier's replay cost vs the miner's mine cost (verify *is*
    re-execution plus digest checks, so the ratio sits near 1 — the
    price of verify-as-state-sync, unlike SAT's certificate asymmetry)
    and the canonical gather-then-hash params digest overhead per
    block."""
    from repro.chain.workload import BlockContext
    from repro.chain.workloads import ModelTrainingWorkload
    from repro.chain.workloads.model_train import MICRO_KWARGS
    from repro.train.steps import params_digest

    miner = ModelTrainingWorkload(**MICRO_KWARGS)
    verifier = ModelTrainingWorkload(**MICRO_KWARGS)

    def ctx(h: int) -> BlockContext:
        return BlockContext(height=h, prev_hash="")

    # block 0 pays the (process-shared) step compile for both chairs
    warm = miner.mine(miner.prepare(ctx(0)))
    if not verifier.verify(warm):
        raise RuntimeError("verifier rejected an honest warmup block")

    t0 = time.perf_counter()
    payloads = [miner.mine(miner.prepare(ctx(1 + i)))
                for i in range(n_blocks)]
    dt_mine = time.perf_counter() - t0
    t0 = time.perf_counter()
    for p in payloads:
        if not verifier.verify(p):
            raise RuntimeError("verifier rejected an honest block")
    dt_verify = time.perf_counter() - t0
    if verifier.state_digest() != miner.state_digest():
        raise RuntimeError("miner/verifier params digests diverged")

    us_mine = dt_mine / n_blocks * 1e6
    us_verify = dt_verify / n_blocks * 1e6
    us_digest = _timeit(lambda: params_digest(miner._state))
    row("model_pouw.mine", us_mine,
        f"blocks_per_s={n_blocks / dt_mine:.1f} "
        f"microsteps={MICRO_KWARGS['block_microsteps']}")
    row("model_pouw.verify", us_verify,
        f"verify_vs_remine={us_verify / us_mine:.2f}x")
    row("model_pouw.digest", us_digest,
        f"pct_of_mine={us_digest / us_mine * 100:.1f}%")
    return {"n_blocks": n_blocks,
            "blocks_per_s": n_blocks / dt_mine,
            "us_mine": us_mine, "us_verify": us_verify,
            "verify_vs_remine": us_verify / us_mine,
            "us_digest": us_digest}


def bench_wire_relay(n_peers: int = 4, n_blocks: int = 6) -> dict:
    """DESIGN §13: compact vs full-body relay over the deterministic
    loopback wire.  Same peers, same seed, same chain — the only
    difference is whether announces inline the payload body or carry
    its 16-byte content checksum (bodies fetched on demand, re-gossip
    deduplicated).  Bytes-on-wire and blocks/s for both; divergence
    between the two chains, or compact failing to save bytes, is a
    hard failure rather than a slow row."""
    from repro.chain.net import loopback_scenario

    schedule = ("classic",) * n_blocks
    # first-touch warmup (suite construction, jit) so neither timed
    # variant pays it
    loopback_scenario(n_peers=2, seed=0, schedule=("classic",),
                      oracle=False)
    results = {}
    for label, compact in (("compact", True), ("full_body", False)):
        t0 = time.perf_counter()
        rep = loopback_scenario(n_peers=n_peers, seed=0, compact=compact,
                                schedule=schedule, oracle=False)
        dt = time.perf_counter() - t0
        if not rep["converged"]:
            raise RuntimeError(f"wire_relay {label}: peers diverged")
        results[label] = (rep, dt)
        row(f"wire_relay.{label}", dt * 1e6,
            f"bytes_on_wire={rep['bytes_on_wire']} "
            f"blocks_per_s={n_blocks / dt:.1f} "
            f"frames={rep['frames_delivered']}")
    (c, dt_c), (f, dt_f) = results["compact"], results["full_body"]
    if c["chain_digest"] != f["chain_digest"]:
        raise RuntimeError("wire_relay: compact and full-body runs "
                           "committed different chains")
    if c["bytes_on_wire"] >= f["bytes_on_wire"]:
        raise RuntimeError(
            f"wire_relay: compact relay saved no bytes "
            f"({c['bytes_on_wire']} vs {f['bytes_on_wire']})")
    saving = 1.0 - c["bytes_on_wire"] / f["bytes_on_wire"]
    row("wire_relay.saving", 0.0,
        f"compact saves {saving:.0%} of wire bytes "
        f"({c['bytes_on_wire']} vs {f['bytes_on_wire']})")
    return {"n_peers": n_peers, "n_blocks": n_blocks,
            "wire_relay_us": dt_c * 1e6,
            "wire_relay_blocks_per_s": n_blocks / dt_c,
            "wire_relay_compact_bytes": c["bytes_on_wire"],
            "wire_relay_full_bytes": f["bytes_on_wire"],
            "wire_relay_saving_frac": saving}


def bench_mesh_discovery(n_peers: int = 5, n_blocks: int = 6) -> dict:
    """DESIGN §14: single-seed mesh bootstrap.  N loopback peers start
    knowing only peer0's address, learn the mesh from HELLO/ADDR
    gossip, dial it full, then mine round-robin.  Rows: wall-clock to
    full mesh, discovery rounds, and the post-discovery convergence
    check — failing to fill the mesh or to converge is a hard failure
    rather than a slow row."""
    from repro.chain.net import mesh_scenario

    schedule = ("classic",) * n_blocks
    # warmup (suite construction, identity derivation) off the clock
    mesh_scenario(n_peers=2, seed=0, schedule=("classic",), oracle=False)
    t0 = time.perf_counter()
    rep = mesh_scenario(n_peers=n_peers, seed=0, schedule=schedule,
                        oracle=False)
    dt = time.perf_counter() - t0
    if not rep["full_mesh"]:
        raise RuntimeError("mesh_discovery: mesh never filled")
    if not rep["converged"]:
        raise RuntimeError("mesh_discovery: peers diverged")
    row("mesh_discovery", rep["discovery_s"] * 1e6,
        f"n_peers={n_peers} rounds={rep['discovery_rounds']} "
        f"addrs_added={rep['addrs_added']} "
        f"bytes_on_wire={rep['bytes_on_wire']} "
        f"blocks_per_s={n_blocks / dt:.1f}")
    return {"n_peers": n_peers, "n_blocks": n_blocks,
            "mesh_discovery_us": rep["discovery_s"] * 1e6,
            "mesh_discovery_rounds": rep["discovery_rounds"],
            "mesh_total_us": dt * 1e6,
            "mesh_bytes_on_wire": rep["bytes_on_wire"],
            "mesh_addrs_added": rep["addrs_added"]}


def bench_mesh_chaos(n_peers: int = 5, n_blocks: int = 10) -> dict:
    """DESIGN §15: time-to-reconverge under everything at once — two
    crash/restart cycles (one with a corrupted journal tail), a 10:1
    addr-flooding eclipse adversary on peer1, and one corrupted frame
    per block.  Failing to reconverge, or the victim losing its last
    honest anchor, is a hard failure rather than a slow row."""
    from repro.chain.net import mesh_chaos_scenario

    schedule = ("classic",) * n_blocks
    faults = ((3, "crash", 2), (3, "corrupt_store", 2), (5, "restart", 2),
              (7, "crash", 3), (8, "restart", 3))
    t0 = time.perf_counter()
    rep = mesh_chaos_scenario(n_peers=n_peers, seed=0, schedule=schedule,
                              faults=faults, oracle=False)
    dt = time.perf_counter() - t0
    if not rep["converged"]:
        raise RuntimeError("mesh_chaos: peers diverged")
    if rep["victim"]["honest_anchors"] < 1:
        raise RuntimeError("mesh_chaos: victim lost every honest anchor")
    row("mesh_chaos", dt * 1e6,
        f"n_peers={n_peers} blocks={n_blocks} "
        f"settle_rounds={rep['settle_rounds']} "
        f"recoveries={len(rep['recoveries'])} "
        f"timeouts={rep['timeouts']} failovers={rep['failovers']} "
        f"honest_anchors={rep['victim']['honest_anchors']}")
    return {"n_peers": n_peers, "n_blocks": n_blocks,
            "mesh_chaos_us": dt * 1e6,
            "mesh_chaos_settle_rounds": rep["settle_rounds"],
            "mesh_chaos_timeouts": rep["timeouts"],
            "mesh_chaos_failovers": rep["failovers"],
            "mesh_chaos_recoveries": len(rep["recoveries"])}


def bench_roofline():
    """Emit the dry-run roofline table (deliverable (g)) as CSV rows."""
    files = sorted(glob.glob("experiments/dryrun/*__single.json"))
    if not files:
        row("roofline.missing", 0.0, "run launch/dryrun first")
        return
    for f in files:
        with open(f) as fh:
            d = json.load(fh)
        if d.get("skipped"):
            row(f"roofline.{d['arch']}.{d['shape']}", 0.0,
                f"SKIP: {d['reason'][:50]}")
            continue
        if "error" in d:
            row(f"roofline.{d['arch']}.{d['shape']}", 0.0, "ERROR")
            continue
        r = d["roofline"]
        t_total = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        row(f"roofline.{d['arch']}.{d['shape']}", t_total * 1e6,
            f"dom={r['dominant']} tc={r['t_compute_s']:.2e} "
            f"tm={r['t_memory_s']:.2e} tx={r['t_collective_s']:.2e} "
            f"useful={d['useful_flops_ratio']:.2f}")


# smoke-scale parameters: the exact shapes --smoke re-measures and the
# full run records as the regression baseline
SMOKE_LEAVES = 256
SMOKE_VERIFY_BLOCKS = 64
SMOKE_VERIFY_ARG_BITS = 8
SMOKE_SUITE = dict(sat_vars=10, sat_clauses=40, grid_bits=6, dock=16,
                   gan_rounds=2, segment=4)


def _git_sha() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:                                  # noqa: BLE001
        return "unknown"


def write_bench_json(payload: dict) -> None:
    """Latest rows at the top level; every run appended to ``history``
    (git sha, date, rows) so the trajectory across PRs is recorded.  A
    pre-history file's top-level rows are folded in as the first
    entry."""
    history = []
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as fh:
                old = json.load(fh)
            history = old.pop("history", [])
            if not history and old:
                history = [{"git_sha": "pre-history", "date": "",
                            "rows": old}]
        except (OSError, json.JSONDecodeError):
            pass
    history.append({"git_sha": _git_sha(),
                    "date": time.strftime("%Y-%m-%d %H:%M:%S"),
                    "rows": payload})
    with open(BENCH_JSON, "w") as fh:
        json.dump({**payload, "history": history}, fh, indent=2)
        fh.write("\n")
    print(f"# wrote {os.path.abspath(BENCH_JSON)} "
          f"({len(history)} history entries)")


def check_smoke_regression(measured: dict) -> int:
    """Gate the reduced-scale metrics against the committed
    ``smoke_baseline``; returns the number of regressions (>2.5x)."""
    try:
        with open(BENCH_JSON) as fh:
            baseline = json.load(fh).get("smoke_baseline")
    except (OSError, json.JSONDecodeError):
        baseline = None
    if not baseline:
        print("# no smoke_baseline in committed BENCH_pipeline.json — "
              "regression gate skipped (run a full bench to record one)")
        return 0
    failures = 0
    for key in ("merkle_commit_us_device", "verify_chain_batched_us",
                "workload_suite_dock_verify_us", "wire_relay_us",
                "mesh_discovery_us", "mesh_chaos_us",
                "model_pouw_verify_us"):
        base, got = baseline.get(key), measured.get(key)
        if base is None or got is None:
            continue
        verdict = "OK"
        if got > base * SMOKE_SLOWDOWN_LIMIT:
            verdict = f"REGRESSION (>{SMOKE_SLOWDOWN_LIMIT}x)"
            failures += 1
        print(f"# gate {key}: measured {got:.0f}us vs baseline "
              f"{base:.0f}us -> {verdict}")
    return failures


def _smoke_scale_metrics(train_section: bool = True,
                         quiet: bool = False) -> dict:
    """The two gated metrics, measured at smoke scale (the full run
    records them as the baseline — with ``quiet`` row suppression so
    reduced-scale timings don't shadow the full-scale rows; --smoke
    re-measures and compares)."""
    global _QUIET
    _QUIET = quiet
    try:
        commit = bench_commit_pipeline(n_leaves=SMOKE_LEAVES,
                                       train_section=train_section)
        verify = bench_verify_pipeline(n_blocks=SMOKE_VERIFY_BLOCKS,
                                       full_arg_bits=SMOKE_VERIFY_ARG_BITS)
        suite = bench_workload_suite(**SMOKE_SUITE)
        wire = bench_wire_relay()
        mesh = bench_mesh_discovery()
        chaos = bench_mesh_chaos()
        model = bench_model_pouw()
    finally:
        _QUIET = False
    return {
        "n_leaves": SMOKE_LEAVES,
        "verify_blocks": SMOKE_VERIFY_BLOCKS,
        "verify_arg_bits": SMOKE_VERIFY_ARG_BITS,
        "suite_scale": SMOKE_SUITE,
        "merkle_commit_us_device": commit["merkle_commit"]["us_device"],
        "verify_chain_batched_us": verify["us_batched"],
        "workload_suite_dock_verify_us": suite["docking"]["us_verify"],
        "wire_relay_us": wire["wire_relay_us"],
        "wire_relay_compact_bytes": wire["wire_relay_compact_bytes"],
        "wire_relay_full_bytes": wire["wire_relay_full_bytes"],
        "mesh_discovery_us": mesh["mesh_discovery_us"],
        "mesh_discovery_rounds": mesh["mesh_discovery_rounds"],
        "mesh_bytes_on_wire": mesh["mesh_bytes_on_wire"],
        "mesh_chaos_us": chaos["mesh_chaos_us"],
        "mesh_chaos_settle_rounds": chaos["mesh_chaos_settle_rounds"],
        "model_pouw_verify_us": model["us_verify"],
        "model_pouw_blocks_per_s": model["blocks_per_s"],
        "model_pouw_verify_vs_remine": model["verify_vs_remine"],
        "model_pouw_digest_us": model["us_digest"],
    }


def main(smoke: bool = False) -> None:
    print("name,us_per_call,derived")
    if smoke:
        # CI subset: commit + verify pipelines at reduced scale (the
        # full-scale numbers are recorded in the committed
        # BENCH_pipeline.json by a full run) + the gossip sim
        # scenarios, then the regression gate against smoke_baseline
        measured = _smoke_scale_metrics()
        bench_sim_gossip()
        bench_recovery(n_blocks=64)
        bench_chaos(n_nodes=8, n_blocks=12)
        failures = check_smoke_regression(measured)
        print(f"# {len(ROWS)} rows (smoke)")
        if failures:
            raise SystemExit(f"{failures} bench regression(s) vs "
                             "committed smoke_baseline")
        return
    fph = bench_hash_flops()
    bench_network_claim(fph)
    bench_block_turnaround()
    bench_mode_overhead()
    bench_pouw_overhead()
    bench_docking()
    bench_verification()
    payload = bench_commit_pipeline()
    payload["verify_pipeline"] = bench_verify_pipeline()
    payload["workload_suite"] = bench_workload_suite()
    payload["sim_gossip"] = bench_sim_scale()
    payload["recovery"] = bench_recovery()
    payload["sim_chaos"] = bench_chaos()
    payload["wire_relay"] = bench_wire_relay()
    payload["mesh_discovery"] = bench_mesh_discovery()
    payload["mesh_chaos"] = bench_mesh_chaos()
    payload["model_pouw"] = bench_model_pouw()
    payload["smoke_baseline"] = _smoke_scale_metrics(train_section=False,
                                                     quiet=True)
    bench_sim_gossip()
    bench_roofline()
    write_bench_json(payload)
    print(f"# {len(ROWS)} rows")


if __name__ == "__main__":
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="fast CI subset (commit pipeline only, small N)")
    args = p.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main(smoke=args.smoke)
