"""The reference's planners, one file per protocol, found by its name.

A protocol's module states its family and how it plans a workload:

* ``FAMILY = "lock"``: transactions take record locks as they run.
  ``ACQUIRE`` says how: ``"interleaved"`` (a lock, then its op; dynamic
  2PL), ``"upfront"`` (every lock in plan order, then the ops) or
  ``"cc_lanes"`` (requests sent through concurrency-control lanes, as in
  ORTHRUS); ``DEADLOCK`` names the deadlock handling (``"none"``,
  ``"waitdie"``, ``"waitfor"``, ``"dreadlocks"``); ``PARTITION_LOCKS``
  marks coarse partition spinlocks.
* ``FAMILY = "batch"``: batches run lock-free over a schedule of planned
  predecessors (``Plan.preds``); a new batch protocol is a new file.

``plan(w, cfg) -> Plan`` plans a workload. The code here is written from
the protocols' stated semantics and imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import importlib

import numpy as np

from ..data import MODE_WRITE, Workload

# padding of the key columns of a transaction
SENTINEL = int(np.iinfo(np.int32).max)


@dataclasses.dataclass
class Plan:
    keys: np.ndarray  # int32[N, K] in request order, SENTINEL padded
    modes: np.ndarray  # int32[N, K]
    part: np.ndarray  # int32[N, K]
    nkeys: np.ndarray  # int32[N]
    exec_ops: np.ndarray  # int32[N]
    ollp: np.ndarray  # bool[N]: reconnaissance before admission
    ollp_miss: np.ndarray  # bool[N]: the first attempt's estimate is wrong
    num_records: int
    # lock family, partition workers: lane_stream[l] = the txns homed to
    # worker l, repeated to one common length (-1 where l has none)
    lane_stream: np.ndarray | None = None
    # batch family: the batch length and each txn's planned predecessors
    epoch: int = 0
    preds: list | None = None


def load(protocol: str):
    """The planner module of ``protocol``; ValueError if there is none."""
    if not protocol.isidentifier():
        raise ValueError(f"unknown protocol {protocol!r}")
    try:
        return importlib.import_module(f"{__name__}.{protocol}")
    except ModuleNotFoundError as e:
        raise ValueError(f"unknown protocol {protocol!r}") from e


def ops(w: Workload, i: int):
    """(column, key, mode, part) of every planned access of txn ``i``."""
    out = []
    for j in range(int(w.nkeys[i])):
        k = int(w.keys[i, j])
        if k != SENTINEL:
            out.append((j, k, int(w.modes[i, j]), int(w.part[i, j])))
    return out


def program_order(w: Workload, *, ollp: bool = True,
                  ollp_miss: bool = True) -> Plan:
    """The workload's own request order (copies of its arrays)."""
    n = len(w.nkeys)
    return Plan(
        keys=w.keys.copy(), modes=w.modes.copy(), part=w.part.copy(),
        nkeys=w.nkeys.copy(), exec_ops=w.exec_ops.copy(),
        ollp=w.ollp.copy() if ollp else np.zeros(n, bool),
        ollp_miss=w.ollp_miss.copy() if ollp_miss else np.zeros(n, bool),
        num_records=w.num_records)


def reorder(w: Workload, sort_key) -> Plan:
    """Each txn's columns stably sorted by ``sort_key(key, part)``."""
    p = program_order(w)
    for i in range(len(w.nkeys)):
        cols = sorted(range(w.keys.shape[1]),
                      key=lambda j: sort_key(int(w.keys[i, j]),
                                             int(w.part[i, j])))
        for a in ("keys", "modes", "part"):
            getattr(p, a)[i] = getattr(w, a)[i, cols]
    return p


def batch_bounds(n: int, epoch: int):
    """(start, size) of each batch of ``epoch`` consecutive txns."""
    b = max(int(epoch), 1)
    return [(s, min(b, n - s)) for s in range(0, n, b)]


def conflict_preds(w: Workload, epoch: int) -> list:
    """Record-level dependencies inside each batch, in serial (txn)
    order: an access depends on the last write to its key before it (a
    read after a write, a write after a write), and a write depends on
    every read of its key since the last write before it (a write after
    a read). A txn's own accesses never make it its own predecessor."""
    preds = [set() for _ in range(len(w.nkeys))]
    for start, size in batch_bounds(len(w.nkeys), epoch):
        last_write: dict = {}  # key -> txn
        reads_since: dict = {}  # key -> txns that read it since then
        for t in range(start, start + size):
            for _j, k, mode, _p in ops(w, t):
                if k in last_write:
                    preds[t].add(last_write[k])
                if mode == MODE_WRITE:
                    preds[t].update(reads_since.pop(k, ()))
                    last_write[k] = t
                else:
                    reads_since.setdefault(k, []).append(t)
            preds[t].discard(t)
    return [sorted(p) for p in preds]


def queue_preds(w: Workload, epoch: int, n_lanes: int) -> list:
    """Per-lane execution queues inside each batch: a txn depends on the
    txn before it in the queue of every lane (``part % n_lanes``) it
    touches."""
    preds = [set() for _ in range(len(w.nkeys))]
    for start, size in batch_bounds(len(w.nkeys), epoch):
        tail: dict = {}  # lane -> last txn queued on it
        for t in range(start, start + size):
            for lane in {p % n_lanes for _j, _k, _m, p in ops(w, t)}:
                if lane in tail:
                    preds[t].add(tail[lane])
                tail[lane] = t
    return [sorted(p) for p in preds]
