"""Workload arrays as the reference simulator reads them."""

from __future__ import annotations

import dataclasses

import numpy as np

MODE_READ = 0
MODE_WRITE = 1


@dataclasses.dataclass
class Workload:
    """One transaction stream: N transactions of at most K lock ops."""

    keys: np.ndarray  # int32[N, K], KEY_SENTINEL padded
    modes: np.ndarray  # int32[N, K]: 0 read, 1 write
    nkeys: np.ndarray  # int32[N]
    part: np.ndarray  # int32[N, K]: partition id per key
    exec_ops: np.ndarray  # int32[N]
    ollp: np.ndarray  # bool[N]
    ollp_miss: np.ndarray  # bool[N]
    num_records: int
    batch_epoch: int
