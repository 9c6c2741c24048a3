"""``repro.spans``: nesting, block keys, counters, the ring's bound, one
stack per thread, the spans of real classic and ``model_train`` blocks
mined and received by two nodes, and the spans in a ``jax.profiler``
capture."""
import glob
import os
import threading
import time

import jax
import pytest

from repro import spans
from repro.chain import ChainStore, Node
from repro.chain.workloads.model_train import (MICRO_KWARGS,
                                               ModelTrainingWorkload)


def _since():
    return time.perf_counter_ns()


def _by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r.name, []).append(r)
    return out


def test_nesting_parents_and_block_key():
    t0 = _since()
    with spans.span("root", node_id=4, height=9) as root:
        with spans.span("child") as child:
            with spans.span("grandchild"):
                pass
        with spans.span("sibling"):
            pass
    recs = _by_name(spans.records(t0))
    r, c = recs["root"][0], recs["child"][0]
    g, s = recs["grandchild"][0], recs["sibling"][0]
    assert r.parent is None and r.seq == root.seq
    assert c.parent == r.seq and s.parent == r.seq
    assert g.parent == c.seq == child.seq
    assert r.key == c.key == g.key == s.key == {"node_id": 4, "height": 9}
    assert r.start_ns <= c.start_ns <= g.start_ns <= g.end_ns \
        <= c.end_ns <= s.start_ns <= s.end_ns <= r.end_ns
    # records come in the order their spans closed
    names = [x.name for x in spans.records(t0)]
    assert names == ["grandchild", "child", "sibling", "root"]


def test_a_span_outside_any_block_has_no_key():
    t0 = _since()
    with spans.span("alone"):
        pass
    (rec,) = spans.records(t0)
    assert rec.parent is None and rec.key is None and rec.counts is None


def test_counts_land_in_the_innermost_span():
    t0 = _since()
    spans.count("outside", 5)                  # no span open: nothing
    with spans.span("outer"):
        spans.count("bytes", 3)
        with spans.span("inner"):
            spans.count("bytes", 10)
            spans.count("bytes", 1)
            spans.count("rows", 2)
        spans.count("bytes", 4)
    recs = _by_name(spans.records(t0))
    assert recs["inner"][0].counts == {"bytes": 11, "rows": 2}
    assert recs["outer"][0].counts == {"bytes": 7}
    assert set(recs) == {"outer", "inner"}


def test_a_span_that_raises_is_recorded_and_leaves_the_stack():
    t0 = _since()
    with pytest.raises(ValueError):
        with spans.span("fails"):
            raise ValueError("no")
    with spans.span("after"):
        pass
    recs = _by_name(spans.records(t0))
    assert recs["after"][0].parent is None
    assert "fails" in recs


def test_elapsed_s_reads_the_open_span():
    with spans.span("timed") as s:
        time.sleep(0.002)
        assert s.elapsed_s() >= 0.002


def test_ring_is_bounded_and_counts_what_it_dropped():
    before = len(spans.records())
    lost = spans.dropped()
    n = spans.RING_SIZE + 10
    for _ in range(n):
        with spans.span("flood"):
            pass
    with spans.span("newest"):
        pass
    recs = spans.records()
    assert len(recs) == spans.RING_SIZE
    assert recs[-1].name == "newest"
    assert all(r.name == "flood" for r in recs[:-1])
    assert spans.dropped() - lost == before + n + 1 - spans.RING_SIZE
    seqs = [r.seq for r in recs]
    assert seqs == sorted(seqs)                # the oldest went first


def test_each_thread_keeps_its_own_stack():
    t0 = _since()
    ready = threading.Barrier(2, timeout=30)

    def work(node_id):
        with spans.span("root", node_id=node_id, height=0):
            ready.wait()                       # both roots open at once
            with spans.span("child"):
                ready.wait()

    threads = [threading.Thread(target=work, args=(i,)) for i in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    recs = spans.records(t0)
    roots = {r.seq: r for r in recs if r.name == "root"}
    children = [r for r in recs if r.name == "child"]
    assert len(roots) == 2 and len(children) == 2
    for c in children:
        assert c.parent in roots
        assert c.key is roots[c.parent].key


# -- the node's blocks --------------------------------------------------------

MINE_CLASSIC = {"ra.publish", "workload.prepare", "workload.mine",
                "executor.run_optimal", "executor.fetch",
                "ledger.merkle_root", "workload.verify", "node.commit"}
MINE_TRAIN = {"workload.prepare", "workload.mine", "model_train.batches",
              "model_train.step", "model_train.proof", "tree_digest.fetch",
              "tree_digest.hash", "ledger.merkle_root", "params_digest",
              "workload.verify", "node.commit", "store.append_commit"}
RECEIVE_TRAIN = {"workload.verify", "model_train.batches",
                 "model_train.step", "model_train.proof",
                 "tree_digest.fetch", "tree_digest.hash",
                 "ledger.merkle_root", "params_digest", "node.commit"}


def _blocks(recs):
    """Root seq -> (root record, every record under it)."""
    by_seq = {r.seq: r for r in recs}
    out = {r.seq: (r, []) for r in recs if r.parent is None}
    for r in recs:
        top = r
        while top.parent is not None:
            top = by_seq[top.parent]
        if top is not r:
            out[top.seq][1].append(r)
    return list(out.values())


def _mine_pair(workload, make_node, n_blocks):
    miner, verifier = make_node(0), make_node(1)
    t0 = _since()
    receipts = []
    for _ in range(n_blocks):
        r = miner.mine_block(workload)
        assert verifier.receive(r.record.to_block(), r.payload, origin=0)
        receipts.append(r)
    return receipts, _blocks(spans.records(t0))


def _coverage(root, under):
    kids = [r for r in under if r.parent == root.seq]
    return sum(k.end_ns - k.start_ns for k in kids) \
        / (root.end_ns - root.start_ns)


def test_classic_blocks_through_miner_and_verifier():
    receipts, blocks = _mine_pair(
        None, lambda i: Node(node_id=i, classic_arg_bits=10), 3)
    mined = [(r, u) for r, u in blocks if r.name == "node.mine_block"]
    got = [(r, u) for r, u in blocks if r.name == "node.receive"]
    assert [r.key for r, _ in mined] == [
        {"node_id": 0, "height": h} for h in range(3)]
    assert [r.key for r, _ in got] == [
        {"node_id": 1, "height": h} for h in range(3)]
    for (root, under), receipt in zip(mined, receipts):
        assert {r.name for r in under} == MINE_CLASSIC
        assert all(r.key is root.key for r in under)
        assert _coverage(root, under) >= 0.9
        # the block's time is its span's time, read before it closed
        assert 0 < receipt.block_time_s <= (root.end_ns
                                            - root.start_ns) * 1e-9
    for root, under in got:
        assert {"workload.verify", "node.commit"} <= {r.name for r in under}


def test_model_train_blocks_through_miner_and_verifier():
    def node(i):
        wl = ModelTrainingWorkload(**MICRO_KWARGS)
        return Node(node_id=i, workloads={"model_train": wl},
                    store=ChainStore(), snapshot_interval=0)

    _, blocks = _mine_pair("model_train", node, 3)
    mined = [(r, u) for r, u in blocks if r.name == "node.mine_block"]
    got = [(r, u) for r, u in blocks if r.name == "node.receive"]
    assert len(mined) == len(got) == 3
    for root, under in mined:
        assert {r.name for r in under} == MINE_TRAIN
        assert _coverage(root, under) >= 0.9
        names = [r.name for r in under]
        assert names.count("params_digest") == 1
        step = next(r for r in under if r.name == "model_train.step")
        assert step.counts["d2h_bytes"] > 0
        journal = next(r for r in under if r.name == "store.append_commit")
        assert journal.counts["journal_bytes"] > 0
        digest = next(r for r in under if r.name == "params_digest")
        fetch = [r for r in under if r.name == "tree_digest.fetch"
                 and r.parent == digest.seq]
        hashed = [r for r in under if r.name == "tree_digest.hash"
                  and r.parent == digest.seq]
        assert len(fetch) == len(hashed) == 1
        # every parameter byte crosses to the host and is hashed, with
        # its framing
        assert 0 < fetch[0].counts["d2h_bytes"] < \
            hashed[0].counts["hashed_bytes"]
    for root, under in got:
        assert {r.name for r in under} >= RECEIVE_TRAIN


def test_spans_appear_in_a_profiler_capture(tmp_path):
    wl = ModelTrainingWorkload(**MICRO_KWARGS)
    node = Node(node_id=0, workloads={"model_train": wl},
                snapshot_interval=0)
    node.mine_block("model_train")             # compile outside the capture
    with jax.profiler.trace(str(tmp_path)):
        node.mine_block("model_train")
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"node.mine_block", "tree_digest.fetch", "params_digest",
            "model_train.step"} <= names
