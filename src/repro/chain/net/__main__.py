"""``python -m repro.chain.net --demo [--peers N]`` — the N-OS-process
TCP mesh convergence oracle (DESIGN.md §13–§14, run by CI's
examples-smoke).

The parent process (worker 0) listens on an ephemeral TCP port — the
**single seed address** — and spawns N-1 child interpreters
(``--role child``).  Every child knows only the seed: it dials it,
learns the rest of the mesh from signed HELLO/ADDR gossip, and dials
the peers its ``PeerBook`` proposes until the mesh is connected.  The
N workers then mine the heterogeneous workload suite round-robin
(block ``k`` is mined by worker ``k mod N``) over real TCP with
signed compact relay.  When every worker sees every other at the
target height, children print their canonical chain digest and credit
book; the parent mines the *same* schedule on an in-process
``Network`` with the same seeds and requires all N+1 — every worker
plus the oracle — to be bit-identical.  Wall-clock is bounded by
``--timeout``.

``--chaos`` is the kill-and-restart variant (wire-level crash
recovery, DESIGN.md §15): worker 1 journals to a durable
``ChainStore`` file; when the mesh reaches the midpoint height the
parent SIGKILLs it — no goodbye, frames in flight lost — and respawns
it with ``--recover``.  The restarted process replays its journal
through ``Node.recover``, redials the seed on a fresh port, resyncs
the lost tail headers-first over TCP, and must still land on the
oracle digest.

Every process of the demo runs JAX on the CPU (``_PLATFORM``), and each
report names the platform it used.  A chip belongs to one process at a
time, so N workers cannot share one; and the GAN blocks of the suite
replay floats, so every worker and the oracle must compute on the same
platform to agree bit for bit.

Exit status 0 iff every chain converged AND matched the in-process
oracle.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import tempfile
import time

import jax

from repro.chain.net.identity import make_addr, make_identities
from repro.chain.net.peer import (_SUITE_SCHEDULE, PeerNode, _suite_node,
                                  chain_digest)
from repro.chain.net.transport import TcpTransport
from repro.chain.node import Node
from repro.chain.store import ChainStore

_RESULT_PREFIX = "RESULT "
_HOST = "127.0.0.1"
# the one platform every process of the demo computes on (module docstring)
_PLATFORM = "cpu"


def _build_peer(idx: int, n_peers: int, *, suite_seed: int,
                store_path: str = "", recover: bool = False):
    """One worker's peer plus the shared identity list (every process
    derives the same deterministic identities, so any worker can
    reconstruct the seed's signed addr locally).  ``store_path``
    attaches a durable journal; ``recover`` replays it through
    ``Node.recover`` instead of starting at genesis — the restarted
    half of the ``--chaos`` demo.

    Liveness windows are generous on real TCP: synchronous mining and
    first-run XLA compilation can stall a worker's event loop for tens
    of seconds, and a spurious keepalive drop just forces a redial."""
    identities, ring = make_identities(n_peers)
    if recover:
        shell = _suite_node(idx, suite_seed=suite_seed, keyring=ring)
        node = Node.recover(ChainStore(store_path), node=shell)
    else:
        node = _suite_node(idx, suite_seed=suite_seed, keyring=ring,
                           store=ChainStore(store_path) if store_path
                           else None)
    peer = PeerNode(node, identities[idx], ring, compact=True,
                    max_peers=2 * n_peers,
                    request_timeout=10.0, ping_interval=15.0,
                    keepalive_timeout=120.0)
    return peer, identities


async def _dial_round(peer: PeerNode, transport: TcpTransport) -> int:
    """Dial every candidate the PeerBook proposes right now."""
    dialed = 0
    for cand in list(peer.dial_candidates()):
        peer.note_dialing(cand.node_id)
        try:
            conn = await transport.connect(cand.host, cand.port,
                                           retries=3, backoff=0.1)
        except ConnectionError:
            peer.note_dial_failed(cand.node_id)
            continue
        peer.on_dialed(conn, cand)
        dialed += 1
    if dialed:
        await transport.drain()
    return dialed


async def _mine_loop(peer: PeerNode, transport: TcpTransport, idx: int,
                     n_peers: int, schedule, deadline: float) -> None:
    """Round-robin over TCP: mine when the tip height is ours, else let
    the reader tasks advance the chain.  Between turns, dial whatever
    the PeerBook has discovered.  After reaching the target, keep
    serving body fetches until every known peer reports the target
    height too (their last blocks may still need our bodies)."""
    loop = asyncio.get_running_loop()
    target = len(schedule)
    last_hello = 0.0
    last_height = -1
    while True:
        if loop.time() > deadline:
            raise TimeoutError(
                f"peer {idx} stuck at height {peer.node.ledger.height} "
                f"knowing {sorted(peer.known_heights().items())}")
        await _dial_round(peer, transport)
        h = peer.node.ledger.height
        if h != last_height:
            # announce every height change at once: a chain pull can
            # jump several heights in one event, and the peers must see
            # the final height before we are allowed to exit — a timer
            # alone races with shutdown
            last_height = h
            last_hello = loop.time()
            peer.broadcast_hello()
            await transport.drain()
        heights = peer.known_heights()
        if (h >= target and len(heights) >= n_peers - 1
                and all(v >= target for v in heights.values())):
            peer.broadcast_hello()       # parting beacon: peers exit too
            await transport.drain()
            return
        now = loop.time()
        if now - last_hello > 0.2:
            last_hello = now
            peer.broadcast_hello()       # height beacon + resync trigger
            await transport.drain()
        # liveness sweep: expire stalled pulls (a killed peer's requests
        # fail over), ping idle conns, drop the silent ones
        peer.tick()
        await transport.drain()
        if h < target and h % n_peers == idx:
            peer.mine_and_announce(schedule[h])
            await transport.drain()
        else:
            await asyncio.sleep(0.02)


def _report(peer: PeerNode, transport: TcpTransport, role: str) -> dict:
    out = {
        "role": role,
        "platform": jax.devices()[0].platform,
        "height": peer.node.ledger.height,
        "chain_digest": chain_digest(peer.node),
        "book": sorted(peer.node.book.balances.items()),
        "chain_valid": peer.node.ledger.verify_chain(),
        "known_ids": sorted(peer.known_heights()),
        "n_conns": len(transport.peer_names()),
        "stats": peer.stats.to_dict(),
        "wire": transport.stats.to_dict(),
    }
    rec = getattr(peer.node, "last_recovery", None)
    if rec is not None:
        out["recovered"] = {"replayed": rec.replayed,
                            "adopted_height": rec.adopted_height,
                            "truncated_records": rec.truncated_records,
                            "resynced_height": rec.resynced_height}
    return out


async def _kill_and_respawn(peer: PeerNode, children: list, child_args,
                            mid: int, deadline: float,
                            verbose: bool) -> dict:
    """The --chaos fault: SIGKILL worker 1 once the parent's chain
    reaches the midpoint height, then respawn it with ``--recover``.
    The journal file survives the kill; everything else — sockets,
    conns, in-flight frames — dies with the process."""
    loop = asyncio.get_running_loop()
    while peer.node.ledger.height < mid:
        if loop.time() > deadline:
            return {"killed": False, "reason": "deadline before midpoint"}
        await asyncio.sleep(0.05)
    proc = children[0]                     # worker 1 is children[0]
    proc.kill()
    out, _ = await loop.run_in_executor(
        None, lambda: proc.communicate(timeout=30))
    if verbose and out:
        print(f"--- killed child output ---\n{out}", file=sys.stderr)
    children[0] = subprocess.Popen(
        child_args + ["--recover"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=dict(os.environ))
    return {"killed": True, "killed_at_height": peer.node.ledger.height,
            "respawned_pid": children[0].pid}


async def _run_child(idx: int, seed_port: int, n_peers: int, *,
                     suite_seed: int, timeout: float, schedule,
                     store_path: str = "", recover: bool = False) -> dict:
    peer, identities = _build_peer(idx, n_peers, suite_seed=suite_seed,
                                   store_path=store_path, recover=recover)
    transport = TcpTransport()
    peer.attach(transport)
    own_port = await transport.listen(_HOST)
    peer.addr = make_addr(identities[idx], _HOST, own_port)
    # single-seed bootstrap: the only address a child starts with is
    # worker 0's (its signed record is reconstructible — identities
    # are deterministic — so it enters the tried bucket like any dial)
    seed_addr = make_addr(identities[0], _HOST, seed_port)
    peer.note_dialing(0)
    conn = await transport.connect(_HOST, seed_port)
    peer.on_dialed(conn, seed_addr)
    deadline = asyncio.get_running_loop().time() + timeout
    await _mine_loop(peer, transport, idx, n_peers, schedule, deadline)
    await transport.drain()
    report = _report(peer, transport, f"child{idx}")
    # linger a moment so late body fetches from slower peers still land
    await asyncio.sleep(0.3)
    await transport.close()
    return report


async def _run_parent(*, n_peers: int, suite_seed: int, timeout: float,
                      verbose: bool, schedule,
                      chaos: bool = False) -> int:
    t0 = time.perf_counter()
    peer, identities = _build_peer(0, n_peers, suite_seed=suite_seed)
    transport = TcpTransport()
    peer.attach(transport)
    port = await transport.listen(_HOST)
    peer.addr = make_addr(identities[0], _HOST, port)
    chaos_dir = tempfile.mkdtemp(prefix="pnp-chaos-") if chaos else None

    def _args_for(i: int) -> list:
        out = [sys.executable, "-m", "repro.chain.net", "--role", "child",
               "--index", str(i), "--port", str(port),
               "--peers", str(n_peers), "--suite-seed", str(suite_seed),
               "--timeout", str(timeout), "--schedule", ",".join(schedule)]
        if chaos and i == 1:
            out += ["--store", os.path.join(chaos_dir, "worker1.journal")]
        return out

    children = [
        subprocess.Popen(_args_for(i),
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True, env=dict(os.environ))
        for i in range(1, n_peers)]
    outputs = []
    fault: dict = {}
    try:
        deadline = asyncio.get_running_loop().time() + timeout
        kill_task = None
        if chaos:
            kill_task = asyncio.create_task(_kill_and_respawn(
                peer, children, _args_for(1),
                mid=max(1, len(schedule) // 2), deadline=deadline,
                verbose=verbose))
        await _mine_loop(peer, transport, 0, n_peers, schedule, deadline)
        await transport.drain()
        if kill_task is not None:
            fault = await kill_task
        for child in children:
            out, _ = await asyncio.get_running_loop().run_in_executor(
                None, lambda c=child: c.communicate(timeout=timeout))
            outputs.append(out)
    except BaseException:
        for child in children:
            if child.poll() is None:
                child.kill()
            try:
                dump, _ = child.communicate(timeout=10)
                print(f"--- child output ---\n{dump}", file=sys.stderr)
            except Exception:
                pass
        raise
    finally:
        for child in children:
            if child.poll() is None:
                child.kill()
        await transport.close()
        if chaos_dir is not None:
            import shutil
            shutil.rmtree(chaos_dir, ignore_errors=True)
    child_reports = []
    for out in outputs:
        found = None
        for line in (out or "").splitlines():
            if line.startswith(_RESULT_PREFIX):
                found = json.loads(line[len(_RESULT_PREFIX):])
        if found is None:
            print(out or "", file=sys.stderr)
            print("FAIL: a child produced no RESULT line", file=sys.stderr)
            return 1
        child_reports.append(found)

    # the in-process oracle: same seeds, same schedule, one interpreter
    from repro.chain.network import Network
    oracle_ids, ring = make_identities(n_peers)
    net = Network.create(
        n_peers, node_factory=lambda i: _suite_node(
            i, suite_seed=suite_seed, keyring=ring),
        identities=oracle_ids)
    net.run(len(schedule), list(schedule))
    oracle_digest = chain_digest(net.nodes[0])
    oracle_book = sorted(net.nodes[0].book.balances.items())

    parent_digest = chain_digest(peer.node)
    parent_book = sorted(peer.node.book.balances.items())
    converged = all(r["chain_digest"] == parent_digest
                    for r in child_reports)
    ok = (converged and parent_digest == oracle_digest
          and parent_book == oracle_book
          and all([tuple(e) for e in r["book"]] == oracle_book
                  for r in child_reports)
          and peer.node.ledger.verify_chain()
          and all(r["chain_valid"] for r in child_reports))
    if chaos:
        # the fault must actually have fired, and the respawned worker
        # must have come back through Node.recover, not from genesis
        ok = (ok and bool(fault.get("killed"))
              and child_reports[0].get("recovered") is not None)
    report = {
        "demo": (f"{n_peers}-process TCP mesh "
                 + ("kill-and-restart recovery" if chaos
                    else "convergence")),
        "n_peers": n_peers,
        "n_blocks": len(schedule),
        "height": peer.node.ledger.height,
        "converged": converged,
        "oracle_match": ok,
        "chain_digest": parent_digest,
        "oracle_digest": oracle_digest,
        "elapsed_s": round(time.perf_counter() - t0, 3),
        "parent": _report(peer, transport, "parent"),
        "children": child_reports,
    }
    if chaos:
        report["fault"] = fault
        report["recovered"] = child_reports[0].get("recovered")
    if verbose:
        print(json.dumps(report, indent=2))
    else:
        brief = {k: report[k] for k in
                 ("n_peers", "converged", "oracle_match",
                  "height", "elapsed_s")}
        brief["platforms"] = [report["parent"]["platform"]] + [
            r["platform"] for r in child_reports]
        if chaos:
            brief["fault"] = fault
            brief["recovered"] = report["recovered"]
        print(json.dumps(brief))
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--demo", action="store_true",
                    help="run the N-process TCP mesh convergence demo")
    ap.add_argument("--peers", type=int, default=2,
                    help="total number of OS processes in the mesh "
                         "(parent + N-1 children; default 2)")
    ap.add_argument("--role", choices=("parent", "child"),
                    default="parent")
    ap.add_argument("--index", type=int, default=1,
                    help="(child) this worker's index in [1, peers)")
    ap.add_argument("--port", type=int, default=0,
                    help="(child) the seed's (parent's) listen port")
    ap.add_argument("--suite-seed", type=int, default=7)
    ap.add_argument("--chaos", action="store_true",
                    help="kill-and-restart variant: SIGKILL worker 1 at "
                         "the midpoint height, respawn it with --recover "
                         "(its journal survives), require oracle parity "
                         "anyway")
    ap.add_argument("--store", default="",
                    help="(child) journal the chain to this file")
    ap.add_argument("--recover", action="store_true",
                    help="(child) replay --store through Node.recover "
                         "before joining the mesh")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="overall wall-clock bound (generous: first-run "
                         "XLA compilation of the workload kernels can "
                         "dominate)")
    ap.add_argument("--schedule", default=",".join(_SUITE_SCHEDULE),
                    help="comma-separated workload families to mine, "
                         "round-robin (default: the full heterogeneous "
                         "suite)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    # before any JAX computation of this process (parent or child)
    jax.config.update("jax_platforms", _PLATFORM)
    schedule = tuple(f for f in args.schedule.split(",") if f)
    if args.peers < 2:
        ap.error("--peers must be >= 2")
    if args.role == "child":
        if not (1 <= args.index < args.peers):
            ap.error("--index must be in [1, peers)")
        if args.recover and not args.store:
            ap.error("--recover needs --store")
        report = asyncio.run(
            _run_child(args.index, args.port, args.peers,
                       suite_seed=args.suite_seed,
                       timeout=args.timeout, schedule=schedule,
                       store_path=args.store, recover=args.recover))
        print(_RESULT_PREFIX + json.dumps(report), flush=True)
        return 0
    if not args.demo:
        ap.error("nothing to do: pass --demo (or --role child)")
    return asyncio.run(
        _run_parent(n_peers=args.peers, suite_seed=args.suite_seed,
                    timeout=args.timeout, verbose=args.verbose,
                    schedule=schedule, chaos=args.chaos))


if __name__ == "__main__":
    raise SystemExit(main())
