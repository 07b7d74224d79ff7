"""Reference simulator: configuration, planning and the host loop.

The benchmark's yardstick for ``correct``. It imports nothing of the
program under test. Planning is the per-protocol planners of
``plans/`` and the key compaction below, written from the protocols'
semantics; the machine's costs are ``machine.json``. The batch family
runs on the serial Python loop of ``batch_loop``, the lock family on the
jitted round step of ``lock_loop``, both written from the families'
semantics. The host loop runs one cell at a time, every chunk resolved
before the next, with the warm-up snapshot at ``warmup_rounds``.

``simulate(cfg, workload)`` returns the counters a cell reads back, as
plain integers: commits, aborts by cause, wasted ops, rounds, executed
steps, the lane-time categories and the commit-latency histogram.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import plans
from .data import Workload
from .machine import MACHINE

# Phases of the lock family's slots
EMPTY, INIT, ACQ, MSG, READY, EXEC, REL, BACKOFF = range(8)
# Sharer-heat epoch length (rounds) of the coherence model
EPOCH_BITS = 12
# Lane-time categories (paper Fig 10 breakdown)
CAT_IDLE, CAT_EXEC, CAT_LOCK, CAT_WAIT, CAT_DL, CAT_MSG = range(6)
NCAT = 6
CAT_NAMES = ("idle", "exec", "lock", "wait", "deadlock", "msg")
# commit-latency histogram buckets (bucket = powers of two <= latency)
LAT_BUCKETS = 24


@dataclasses.dataclass(frozen=True)
class RefConfig:
    """A protocol on a simulated machine, with its round budget."""

    protocol: str
    n_exec: int
    n_cc: int = 0
    window: int = 1
    split_index: bool = False
    event_leap: bool = True
    max_rounds: int = 16_000
    warmup_rounds: int = 4_000
    chunk_rounds: int = 4_000
    target_commits: int = 100_000_000
    # False only in the control: conflicting requests are granted together
    # and planned predecessors are not waited for (no isolation)
    isolation: bool = True

    def __post_init__(self):
        plans.load(self.protocol)

    @property
    def planner(self):
        return plans.load(self.protocol)

    @property
    def cost(self):
        return MACHINE

    @property
    def n_slots(self) -> int:
        return self.n_exec * self.window

    @property
    def is_batch_planned(self) -> bool:
        return self.planner.FAMILY == "batch"

    @property
    def acquire(self) -> str:
        return self.planner.ACQUIRE

    @property
    def is_orthrus(self) -> bool:
        return self.acquire == "cc_lanes"

    @property
    def is_dynamic_2pl(self) -> bool:
        return self.acquire == "interleaved"

    @property
    def partition_locks(self) -> bool:
        return getattr(self.planner, "PARTITION_LOCKS", False)

    @property
    def deadlock_scheme(self) -> str:
        return self.planner.DEADLOCK

    def statics(self) -> tuple:
        """The fields the compiled lock loop depends on."""
        return (self.protocol, self.n_exec, self.n_cc, self.window,
                self.split_index, self.event_leap, self.isolation)


@dataclasses.dataclass(frozen=True)
class PlanMeta:
    """Shapes of a lock-family plan: what its compiled loop is built for."""

    n_txns: int
    max_keys: int
    num_records: int
    lane_cols: int = 0


def compact_keys(plan: plans.Plan) -> plans.Plan:
    """Number the records a plan touches 0, 1, ... in key order (padding
    stays padding), and size the lock table to the least power of two,
    at least 16, that holds them with a quarter to spare."""
    used = sorted({int(k) for k in plan.keys.ravel()} - {plans.SENTINEL})
    rank = {k: i for i, k in enumerate(used)}
    rank[plans.SENTINEL] = plans.SENTINEL
    keys = np.array([[rank[int(k)] for k in row] for row in plan.keys],
                    np.int32).reshape(plan.keys.shape)
    need = max(len(used), 1)
    need += need // 4
    size = 16
    while size < need:
        size *= 2
    return dataclasses.replace(plan, keys=keys, num_records=size)


def make_plan(cfg: RefConfig, w: Workload) -> plans.Plan:
    plan = cfg.planner.plan(w, cfg)
    return plan if cfg.is_batch_planned else compact_keys(plan)


def chunk_boundaries(cfg: RefConfig):
    """Host-loop chunk boundaries: the ``chunk_rounds`` grid (the last may
    pass ``max_rounds``), with one more at ``warmup_rounds`` when it is
    off the grid."""
    r = 0
    while r < cfg.max_rounds:
        nxt = (r // cfg.chunk_rounds + 1) * cfg.chunk_rounds
        if r < cfg.warmup_rounds < nxt:
            nxt = cfg.warmup_rounds
        yield nxt
        r = nxt


_COUNTERS = ("commits", "aborts_dl", "aborts_ollp", "wasted", "next_txn",
             "steps", "cat", "lat_hist")
_RUNNERS: dict = {}


class _LockLoop:
    """The lock family's round loop, jitted per plan shape."""

    def __init__(self, cfg: RefConfig, plan: plans.Plan):
        import jax

        from . import lock_loop

        meta = PlanMeta(
            n_txns=plan.keys.shape[0], max_keys=plan.keys.shape[1],
            num_records=plan.num_records,
            lane_cols=0 if plan.lane_stream is None
            else plan.lane_stream.shape[1])
        p = dict(keys=plan.keys, modes=plan.modes, part=plan.part,
                 nkeys=plan.nkeys, exec_ops=plan.exec_ops, ollp=plan.ollp,
                 ollp_miss=plan.ollp_miss)
        if plan.lane_stream is not None:
            p["lane_stream"] = plan.lane_stream
        self.p = jax.device_put(
            {k: np.asarray(v, bool if v.dtype == bool else np.int32)
             for k, v in p.items()})
        self.state = lock_loop.state0(cfg, meta.num_records, cfg.n_slots,
                                      meta.max_keys)
        key = (cfg.statics(), meta)
        if key not in _RUNNERS:
            step = lock_loop.make_step(cfg, meta)

            def run_chunk(p, state, r_end):
                return jax.lax.while_loop(
                    lambda s: s["r"] < r_end,
                    lambda s: step(p, s, r_end), state)

            _RUNNERS[key] = jax.jit(run_chunk, donate_argnums=1)
        self.run = _RUNNERS[key]

    def run_to(self, r_end: int) -> None:
        import jax.numpy as jnp

        self.state = self.run(self.p, self.state,
                              jnp.asarray(r_end, jnp.int32))

    def counters(self) -> dict:
        return {k: np.asarray(self.state[k]).astype(np.int64)
                for k in _COUNTERS}


def simulate(cfg: RefConfig, w: Workload) -> dict:
    """Run one cell serially and return its measured counters."""
    from .batch_loop import BatchLoop

    plan = make_plan(cfg, w)
    loop = BatchLoop(cfg, plan) if cfg.is_batch_planned else _LockLoop(
        cfg, plan)

    def read():
        return {k: np.asarray(v, np.int64)
                for k, v in loop.counters().items()}

    warm = {k: np.zeros_like(v) for k, v in read().items()}
    warm_rounds = 0
    last = warm
    r_done = 0
    for b in chunk_boundaries(cfg):
        loop.run_to(b)
        last = read()
        r_done = b
        if b <= cfg.warmup_rounds:
            warm, warm_rounds = last, b
        if last["commits"] - warm["commits"] >= cfg.target_commits:
            break
    cat = last["cat"] - warm["cat"]
    total_lane_rounds = max(int(cat.sum()), 1)
    return dict(
        commits=int(last["commits"] - warm["commits"]),
        aborts_deadlock=int(last["aborts_dl"] - warm["aborts_dl"]),
        aborts_ollp=int(last["aborts_ollp"] - warm["aborts_ollp"]),
        wasted_ops=int(last["wasted"] - warm["wasted"]),
        rounds=int(r_done - warm_rounds),
        rounds_total=int(r_done),
        total_commits=int(last["commits"]),
        next_txn=int(last["next_txn"]),
        steps_executed=int(last["steps"]),
        breakdown={nm: float(cat[k]) / total_lane_rounds
                   for k, nm in enumerate(CAT_NAMES)},
        lat_hist=[int(x) for x in last["lat_hist"] - warm["lat_hist"]],
    )
