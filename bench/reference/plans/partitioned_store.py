"""Partitioned store (H-Store): a transaction locks the partitions it
touches (partition mod n_exec), exclusively and in ascending order, and
runs on the worker of its lowest partition."""

import numpy as np

from . import SENTINEL, Plan, ops
from ..data import MODE_WRITE

FAMILY = "lock"
ACQUIRE = "upfront"
DEADLOCK = "none"
PARTITION_LOCKS = True


def plan(w, cfg):
    n, k = w.keys.shape
    parts = cfg.n_exec
    keys = np.full((n, k), SENTINEL, np.int32)
    part = np.zeros((n, k), np.int32)
    nkeys = np.zeros(n, np.int32)
    homed = [[] for _ in range(parts)]
    for i in range(n):
        mine = sorted({p % parts for _j, _k, _m, p in ops(w, i)})
        keys[i, :len(mine)] = mine
        part[i, :len(mine)] = mine
        nkeys[i] = len(mine)
        homed[(mine[0] if mine else SENTINEL) % parts].append(i)
    m = max(1, max(len(h) for h in homed))
    lane_stream = np.full((parts, m), -1, np.int32)
    for lane, h in enumerate(homed):
        if h:
            lane_stream[lane] = [h[j % len(h)] for j in range(m)]
    return Plan(keys=keys, modes=np.full((n, k), MODE_WRITE, np.int32),
                part=part, nkeys=nkeys, exec_ops=w.exec_ops.copy(),
                ollp=np.zeros(n, bool), ollp_miss=np.zeros(n, bool),
                num_records=parts, lane_stream=lane_stream)
