"""Spans and counters of the node's block path.

A span names one stretch of a block's work::

    with spans.span("node.mine_block", node_id=0, height=7) as blk:
        with spans.span("params_digest"):
            spans.count("d2h_bytes", n)
        blk.elapsed_s()              # seconds since the span opened

Each span opens a ``jax.profiler.TraceAnnotation`` under its name, so a
``jax.profiler`` capture shows it on the host timeline beside the device
ops it waited for.  On exit it appends one ``Record`` to a bounded
in-memory ring: name, start and end on ``time.perf_counter_ns()``, its
sequence number and its parent's, the block key and the counts made in
it.  A span given attributes starts a new block key (the node's root
spans give ``node_id`` and ``height``); every other span shares the key of
the span it opened under.  Parents follow a per-thread stack, so spans of
the network's threads never adopt each other's.

``count(name, n)`` adds ``n`` to the innermost open span's counts, where
the work happens; outside any span it does nothing.  ``records()`` reads
the ring, oldest first, and ``dropped()`` says how many records it has
lost.  There is no switch: the ring always records, like the network's
counters, and an annotation costs next to nothing while no profiler runs.
"""
from __future__ import annotations

import collections
import itertools
import threading
from time import perf_counter_ns
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["RING_SIZE", "Record", "count", "dropped", "records", "span"]

# A miner and a verifier on classic blocks close 14 spans per block, and
# on one TPU v5e 2,030 blocks of 2**20 nonces in 51 s: some 28,000
# records.  2**16 holds that minute whole, with room for what came before.
RING_SIZE = 1 << 16


class Record(NamedTuple):
    """One closed span.  ``parent`` is the enclosing span's ``seq`` (None
    at a root); ``key`` the attributes of the span that started the block
    (``node_id``, ``height``), shared by everything under it."""
    name: str
    start_ns: int                  # time.perf_counter_ns()
    end_ns: int
    seq: int
    parent: Optional[int]
    key: Optional[Dict[str, Any]]
    counts: Optional[Dict[str, int]]


_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_lock = threading.Lock()
_appended = 0
_seq = itertools.count()
_local = threading.local()


def _stack() -> List["span"]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class span:
    """Context manager for one span; ``attrs`` start a new block key."""

    __slots__ = ("name", "key", "counts", "seq", "parent", "start_ns",
                 "_note")

    def __init__(self, name: str, **attrs: Any) -> None:
        self.name = name
        self.key = attrs or None

    def __enter__(self) -> "span":
        stack = _stack()
        outer = stack[-1] if stack else None
        self.parent = outer.seq if outer is not None else None
        if self.key is None and outer is not None:
            self.key = outer.key
        self.counts = None
        self.seq = next(_seq)
        stack.append(self)
        self._note = TraceAnnotation(self.name)
        self._note.__enter__()
        self.start_ns = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = perf_counter_ns()
        self._note.__exit__(*exc)
        _stack().pop()
        rec = Record(self.name, self.start_ns, end, self.seq, self.parent,
                     self.key, self.counts)
        global _appended
        with _lock:
            _ring.append(rec)
            _appended += 1

    def elapsed_s(self) -> float:
        """Seconds since this span opened."""
        return (perf_counter_ns() - self.start_ns) * 1e-9


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span."""
    stack = getattr(_local, "stack", None)
    if stack:
        top = stack[-1]
        if top.counts is None:
            top.counts = {name: n}
        else:
            top.counts[name] = top.counts.get(name, 0) + n


def records(since_ns: Optional[int] = None) -> List[Record]:
    """The ring's records in the order their spans closed, those that
    started at or after ``since_ns`` if given."""
    with _lock:
        out = list(_ring)
    if since_ns is not None:
        out = [r for r in out if r.start_ns >= since_ns]
    return out


def dropped() -> int:
    """Records the ring has lost, oldest first, since the process began."""
    with _lock:
        return _appended - len(_ring)
