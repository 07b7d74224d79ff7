"""Shared-compilation sweep driver for the ORTHRUS engine.

The paper's figures are sweeps — protocols x contention x thread counts x
workloads — and the expensive part of every cell used to be a fresh XLA
compile: plan arrays were baked into ``make_step`` as constants. This
module separates *what varies per cell* (the traced plan/workload arrays)
from *what forces recompilation* (protocol statics + array shapes):

  * :func:`get_runner` — a process-wide LRU cache of jitted round-chunk
    runners keyed on ``(EngineConfig.trace_statics(), PlanMeta)``. One
    compilation serves every cell of a figure that shares the key (the
    chunk bound ``r_end`` is a traced argument, so cells may even differ
    in simulation budget).
  * :func:`simulate_plans` — the host loop (warmup snapshot, chunked
    round execution, per-cell termination) over one *or several*
    same-shape plans. Multiple plans are stacked and driven through a
    single ``jax.vmap``-ed runner: one compiled program advances every
    cell of a sweep concurrently, and each cell's counters are captured
    at exactly the chunk boundary where the serial loop would have
    stopped, so results are identical to running cells one at a time
    (property-tested in ``tests/test_engine_leap.py``).
  * :func:`run_cells` — batch API over (config, workload) cells: plans
    each cell, groups by compile key, and vmaps each group.

Sweep-scale parallelism (:class:`SweepMode`)
--------------------------------------------
The driver composes three attacks, each bit-identical to the serial
per-cell loop by construction (``SERIAL_MODE`` disables all three; the
default :func:`sweep_mode` enables them from the environment):

  * **device sharding** (``mode.devices``, ``REPRO_SWEEP_DEVICES``) —
    the leading cell axis of each vmapped group is sharded across a 1-D
    ``jax.sharding.Mesh`` (``sharding.policies.cell_mesh``), padding the
    cell count to a device multiple with inert duplicate lanes
    (``r_end=0``: their while-loop condition is false on entry, so they
    cost one predicate evaluation, and their results are discarded).
    Identity holds because vmapped lanes never interact: sharding only
    changes *where* a lane's independent computation runs.
  * **pipelined asynchronous host loop** (``mode.pipeline``,
    ``REPRO_SWEEP_PIPELINE``) — JAX dispatch is asynchronous, so the
    host enqueues chunk k+1 (donating the carried state) before
    resolving chunk k's counters from small device-side ``jnp.copy``
    snapshots taken at each boundary; only the counter pytree crosses
    to the host. :func:`run_cells` additionally dispatches the *next*
    group's first chunk while the current group executes, overlapping
    compile with execution. Identity holds because counters are still
    read at the same chunk boundaries in the same order — a cell that
    meets ``target_commits`` at boundary k is snapshotted from boundary
    k's copy even though boundary k+1 was already in flight.
  * **per-cell early exit** (``mode.early_exit``,
    ``REPRO_SWEEP_EARLY_EXIT``) — ``r_end`` is a traced *per-cell
    vector* under vmap: once a cell's counters are snapshotted, its
    lane's bound drops to 0 and the vmapped while-loop's select-masking
    freezes it (exactly the mechanism that already lets lanes of one
    group leap different amounts per iteration), so heterogeneous
    groups stop burning rounds on finished cells. Identity holds
    because a frozen lane's state is bit-preserved and its counters
    were already captured.

Warmup accounting: the warmup snapshot subtracts *all four* counters
(commits, deadlock aborts, OLLP aborts, wasted ops) plus the lane-time
breakdown, consistently — previously ``aborts_ollp``/``wasted_ops`` were
reported raw while the others subtracted the snapshot. Optional engine
counters (``_OPT_SCALARS`` — pipelined-admission and planner-lane
telemetry) ride the same snapshot discipline into ``SimResult.raw``.
When ``warmup_rounds`` is not a multiple of ``chunk_rounds``, the chunk
containing it is split at the warmup boundary (then the schedule
returns to the original chunk grid), so the snapshot lands exactly at
``warmup_rounds`` instead of silently at the last smaller boundary.

Cache-invalidation contract
---------------------------
Two caches with sharply different rules hang off this module:

  * ``_RUNNER_CACHE`` (process-local, compiled runners): keyed on
    ``(EngineConfig.trace_statics(), PlanMeta, batched, mesh)``. Every config
    field that changes the *traced computation* must appear in
    ``trace_statics()`` (a false hit silently simulates the wrong
    protocol); host-loop budget fields must not (a false miss recompiles
    per cell). Traced *values* — plan arrays, the epoch-rate scalar —
    never invalidate it. ``tests/test_sweep_cache.py`` audits every
    ``EngineConfig`` field into one of the two classes. The cache is a
    bounded LRU (``REPRO_SWEEP_RUNNER_CACHE``, default 256 entries):
    compiled executables pin device memory, so long multi-figure runs
    evict least-recently-used runners instead of growing without bound.
  * benchmark result caches (``benchmarks/common.py``, on disk): keyed
    on a hash that includes :data:`ENGINE_VERSION`. Any result-visible
    engine change must bump the version so stale numbers become
    unreachable; bit-identical refactors must *not* bump it (the golden
    traces prove bit-identity, and cached figure cells stay valid).
"""

from __future__ import annotations

import dataclasses
import os
from collections import OrderedDict, deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as engine_lib
from repro.core import metrics as metrics_lib
from repro.core.engine import EngineConfig, NCAT, PlanMeta, SimResult
from repro.core.workloads import Workload
from repro.sharding import policies as sharding_policies

# Engine-code version tag. Bump whenever step semantics, accounting, or
# planner output change in any result-visible way: benchmark caches
# (benchmarks/common.py) hash this tag into their keys so stale cache
# entries from an older engine can never silently mix with fresh ones.
# ("3-packed-slots" is bit-identical to "2-event-leap" by construction —
# golden traces enforce it — but carries a different performance profile,
# so perf samples keyed on the old tag must not mix with new ones.
# "4-mega-dispatch" — K-round fused dispatch + compact CSR release/
# wait-for + enqueue-stamp rebasing — is likewise bit-identical at every
# rounds_per_dispatch, with a different performance profile. The
# sharded/pipelined/early-exit sweep driver is bit-identical to the
# serial driver in every mode, so it does NOT bump the tag.)
ENGINE_VERSION = "4-mega-dispatch"


@dataclasses.dataclass(frozen=True)
class SweepMode:
    """How the sweep driver parallelizes a group of cells.

    Every combination is bit-identical to ``SERIAL_MODE`` (the PR 8
    driver semantics: one device, resolve every chunk synchronously,
    run every cell to the group's last boundary).

      * ``devices`` — shard the vmapped cell axis across this many local
        devices (clamped to what exists; 1 = no sharding).
      * ``pipeline`` — how many unresolved chunk boundaries may be in
        flight per group (0 = fully synchronous host loop). Any depth
        > 0 also lets :func:`run_cells` overlap the next group's first
        compile+dispatch with the current group's execution.
      * ``early_exit`` — freeze a cell's lane (per-cell traced ``r_end``)
        once its counters are snapshotted at ``target_commits``.
    """

    devices: int = 1
    pipeline: int = 1
    early_exit: bool = True


# The reference driver: semantics of the pre-sharding serial host loop.
SERIAL_MODE = SweepMode(devices=1, pipeline=0, early_exit=False)


def sweep_mode() -> SweepMode:
    """The environment-selected driver mode.

    ``REPRO_SWEEP_DEVICES`` — device count for cell-axis sharding
    ("auto"/"0"/unset = all local devices; CI forces >1 via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    ``REPRO_SWEEP_PIPELINE`` — in-flight chunk depth (default 1).
    ``REPRO_SWEEP_EARLY_EXIT`` — per-cell early exit (default on).
    """
    raw = os.environ.get("REPRO_SWEEP_DEVICES", "auto").strip().lower()
    if raw in ("", "auto", "0"):
        devices = jax.local_device_count()
    else:
        devices = max(1, int(raw))
    pipeline = max(0, int(os.environ.get("REPRO_SWEEP_PIPELINE", "1")))
    early = os.environ.get("REPRO_SWEEP_EARLY_EXIT", "1").strip().lower()
    return SweepMode(
        devices=devices,
        pipeline=pipeline,
        early_exit=early not in ("0", "false", "off"),
    )


# Bounded LRU of compiled chunk runners (most-recently-used last).
_RUNNER_CACHE: OrderedDict = OrderedDict()
_RUNNER_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_RUNNER_CACHE_CAPACITY = max(
    1, int(os.environ.get("REPRO_SWEEP_RUNNER_CACHE", "256"))
)

_SCALARS = ("commits", "aborts_dl", "aborts_ollp", "wasted", "next_txn", "steps")
# Present only in some engine states; each is cumulative and reported
# warmup-subtracted in ``SimResult.raw``:
#   pipe_adm / pipe_commits — inter-batch pipelined admission: traffic
#     that ran ahead of the batch barrier (per-batch accounting split);
#   plan_busy / plan_qdelay / epoch_ctr — planner-lane throughput model:
#     lane-busy planning rounds (amortized: a batch's whole work span is
#     charged at rollover), rounds batch plans spent queued behind busy
#     lanes, and batches planned. ``epoch_ctr`` also appears under open
#     epoch arrival alone.
#   plan_busy_int — round-granular lane-busy integral: only rounds that
#     have actually elapsed count, so utilization
#     plan_busy_int / (L * rounds) never transiently exceeds 1 (the
#     fig15 fix; plan_busy keeps the amortized semantics the planner
#     oracle tests pin).
#   pol_* — overload-robustness layer (engine.EngineConfig): admission
#     drops (pol_rejected = bounded_backlog, pol_shed = deadline_shed
#     queue drops, pol_timedout = in-flight deadline give-ups),
#     token-bucket admissions (pol_tb_adm), retry-budget give-ups
#     (pol_sacrificed) and total exponential-backoff rounds issued
#     (pol_backoff_rounds).
_OPT_SCALARS = (
    "pipe_adm", "pipe_commits", "plan_busy", "plan_qdelay", "epoch_ctr",
    "plan_busy_int",
    "pol_rejected", "pol_shed", "pol_timedout", "pol_tb_adm",
    "pol_sacrificed", "pol_backoff_rounds",
)

# Metrics counter arrays carried by the packed engine (the legacy-layout
# oracle predates them): cumulative latency histogram, point-sampled
# queue trajectories (see repro.core.metrics).
_METRIC_ARRAYS = (
    ("lat_hist", metrics_lib.LAT_BUCKETS),
    ("q_depth", metrics_lib.QDEPTH_SAMPLES),
    ("q_inflight", metrics_lib.QDEPTH_SAMPLES),
)
_METRIC_WIDTH = dict(_METRIC_ARRAYS)


def runner_cache_info() -> dict:
    """Introspection for tests/tools: cached compiled runners + LRU
    hit/miss/eviction counters (cumulative per process)."""
    return {
        "entries": len(_RUNNER_CACHE),
        "keys": list(_RUNNER_CACHE),
        "capacity": _RUNNER_CACHE_CAPACITY,
        **_RUNNER_CACHE_STATS,
    }


def set_runner_cache_capacity(capacity: int) -> int:
    """Set the LRU bound (evicting down to it); returns the old bound."""
    global _RUNNER_CACHE_CAPACITY
    old = _RUNNER_CACHE_CAPACITY
    _RUNNER_CACHE_CAPACITY = max(1, int(capacity))
    while len(_RUNNER_CACHE) > _RUNNER_CACHE_CAPACITY:
        _RUNNER_CACHE.popitem(last=False)
        _RUNNER_CACHE_STATS["evictions"] += 1
    return old


def _step_module(cfg: EngineConfig):
    """The step-builder module for the config's state layout: the packed
    [T, F] engine, or the frozen pre-rewrite reference
    (``repro.core.engine_legacy``) used as the conformance oracle."""
    if cfg.state_layout == "legacy":
        from repro.core import engine_legacy

        return engine_legacy
    return engine_lib


def get_runner(cfg: EngineConfig, meta: PlanMeta, batched: bool,
               mesh=None):
    """The jitted chunk runner for this (config-statics, plan-shape) key.

    ``runner(p, state, r_end)`` advances ``state`` to round ``r_end``
    (event-leaping when ``cfg.event_leap``); with ``batched=True`` the
    runner is vmapped over a leading cell axis of ``p``, ``state`` *and*
    ``r_end`` — the per-cell round bound is what lets finished cells
    freeze (early exit) while their groupmates keep running. With a
    ``mesh`` (``sharding.policies.cell_mesh``) the vmapped runner is
    ``shard_map``-ed over its ``"cells"`` axis: each device runs its own
    cells' loop, and Pallas kernels, which XLA cannot partition, see
    only device-local arrays.
    """
    key = (cfg.trace_statics(), meta, batched, mesh)
    fn = _RUNNER_CACHE.get(key)
    if fn is not None:
        _RUNNER_CACHE.move_to_end(key)
        _RUNNER_CACHE_STATS["hits"] += 1
        return fn
    _RUNNER_CACHE_STATS["misses"] += 1
    step_mod = _step_module(cfg)
    builder = (
        step_mod.make_batch_step
        if cfg.is_batch_planned
        else step_mod.make_step
    )
    step = builder(cfg, meta)
    # K-round mega-dispatch: each while_loop iteration (one XLA
    # dispatch) runs up to K = cfg.dispatch_rounds steps, amortizing
    # the fixed per-op dispatch overhead of the round body. Inner
    # steps past the first are guarded by `r < r_end` (a lax.cond:
    # the skipped branch costs nothing unbatched, a select under
    # vmap), so state at every chunk boundary — and therefore every
    # counter, including steps_executed — is bit-identical to K=1.
    # Event leaping runs per inner step, unchanged.
    K = cfg.dispatch_rounds
    # enqueue-stamp rebase at dispatch boundaries (packed lock-table
    # engines only): bounds the monotone enq_ctr by in-flight
    # requests so it cannot wrap at long horizons. Bit-exact — grant
    # decisions depend only on stamp differences among live entries.
    rebase = (
        cfg.state_layout == "packed" and not cfg.is_batch_planned
    )

    def run_chunk(p, state, r_end):
        def dispatch(s):
            if rebase:
                s = engine_lib.rebase_enq(s)
            s = step(p, s, r_end)
            for _ in range(K - 1):
                s = jax.lax.cond(
                    s["r"] < r_end,
                    lambda st: step(p, st, r_end),
                    lambda st: st,
                    s,
                )
            return s

        return jax.lax.while_loop(
            lambda s: s["r"] < r_end,
            dispatch,
            state,
        )

    if batched:
        # per-cell r_end: a lane whose bound is behind its round counter
        # fails the (select-masked) loop condition and keeps its state
        # bit-identical — the early-exit freeze. A uniform vector
        # reproduces the old broadcast-scalar driver exactly.
        run_chunk = jax.vmap(run_chunk, in_axes=(0, 0, 0))
    if mesh is not None:
        cells = jax.sharding.PartitionSpec("cells")
        # no collectives: every leaf is device-local on the cell axis
        # (check_vma off, since Pallas out_shapes carry no vma)
        run_chunk = jax.shard_map(
            run_chunk, mesh=mesh, in_specs=cells, out_specs=cells,
            check_vma=False,
        )
    fn = jax.jit(run_chunk, donate_argnums=1)
    _RUNNER_CACHE[key] = fn
    while len(_RUNNER_CACHE) > _RUNNER_CACHE_CAPACITY:
        _RUNNER_CACHE.popitem(last=False)
        _RUNNER_CACHE_STATS["evictions"] += 1
    return fn


def chunk_boundaries(cfg: EngineConfig):
    """Yield the host-loop chunk boundaries for one simulation budget.

    Boundaries fall on the ``chunk_rounds`` grid (the final one may
    overshoot ``max_rounds``, exactly like the serial loop), with one
    extra boundary inserted at ``warmup_rounds`` when it is not itself
    on the grid — so the warmup snapshot is taken at the warmup round,
    not silently at the last smaller chunk boundary. After the split
    the schedule returns to the original grid, leaving every other
    boundary (and the max_rounds overshoot) unchanged.
    """
    r = 0
    while r < cfg.max_rounds:
        nxt = (r // cfg.chunk_rounds + 1) * cfg.chunk_rounds
        if r < cfg.warmup_rounds < nxt:
            nxt = cfg.warmup_rounds
        yield nxt
        r = nxt


def _counter_keys(state) -> list[str]:
    keys = list(_SCALARS)
    keys += [k for k in _OPT_SCALARS if k in state]
    keys.append("cat")
    keys += [k for k, _ in _METRIC_ARRAYS if k in state]
    return keys


def _snapshot_counters(state) -> dict:
    """Device-side copies of the small per-cell counters.

    The copies are enqueued *before* the next chunk donates ``state``'s
    buffers, so a pipelined host loop can resolve them after the fact
    without ever synchronizing on (or preserving) the full state.
    """
    return {k: jnp.copy(state[k]) for k in _counter_keys(state)}


def _counters_to_host(snap: dict, n: int) -> dict[str, np.ndarray]:
    """Device -> host transfer of a counter snapshot (blocks until the
    producing chunk has executed)."""
    out = {}
    for k, v in snap.items():
        if k == "cat":
            out[k] = np.asarray(v).reshape(n, NCAT)
        elif k in _METRIC_WIDTH:
            out[k] = np.asarray(v).reshape(n, _METRIC_WIDTH[k])
        else:
            out[k] = np.atleast_1d(np.asarray(v))
    return out


def _read_counters(state, n: int) -> dict[str, np.ndarray]:
    """Device -> host transfer of the small per-cell counters."""
    return _counters_to_host(
        {k: state[k] for k in _counter_keys(state)}, n
    )


def _zeros_like_counters(n: int) -> dict[str, np.ndarray]:
    out = {k: np.zeros((n,), np.int64) for k in _SCALARS}
    out["cat"] = np.zeros((n, NCAT), np.int64)
    return out


def _cell_slice(host: dict[str, np.ndarray], i: int) -> dict[str, np.ndarray]:
    return {k: np.array(v[i], copy=True) for k, v in host.items()}


class _GroupRun:
    """One statics-shaped group of cells driven to completion.

    Owns the padded/stacked/sharded plan + state, the chunk-boundary
    schedule, the pipelined dispatch/resolve queue, and per-cell
    warmup/termination snapshots. Cells may carry *different* traced
    values (plan arrays, epoch rates, policy knobs) and different
    ``EngineConfig``s, as long as every config shares
    ``trace_statics()``, the host-loop budget, and plan shapes.
    """

    def __init__(self, cfgs: list[EngineConfig], plans: list,
                 mode: SweepMode, ps: list | None = None):
        n = len(plans)
        assert n == len(cfgs) and n > 0
        cfg0 = cfgs[0]
        assert len({c.trace_statics() for c in cfgs}) == 1, (
            "grouped cells must share trace statics"
        )
        assert len({
            (c.max_rounds, c.warmup_rounds, c.chunk_rounds, c.target_commits)
            for c in cfgs
        }) == 1, "grouped cells must share the host-loop budget"
        metas = {
            engine_lib.plan_meta(c, pl) for c, pl in zip(cfgs, plans)
        }
        assert len(metas) == 1, f"plans must share shapes, got {metas}"
        self.meta = next(iter(metas))
        self.cfgs, self.plans, self.mode, self.n = cfgs, plans, mode, n

        with jax.profiler.TraceAnnotation("repro.upload"):
            if ps is None:
                ps = [
                    engine_lib.plan_device(c, pl)
                    for c, pl in zip(cfgs, plans)
                ]
            T = cfg0.n_slots
            step_mod = _step_module(cfg0)
            if cfg0.is_batch_planned:
                states = [
                    step_mod._batch_state0(c, pl, T)
                    for c, pl in zip(cfgs, plans)
                ]
            else:
                states = [
                    step_mod._state0(c, pl.num_records, T, self.meta.max_keys)
                    for c, pl in zip(cfgs, plans)
                ]

            # device layout: pad the cell axis to a multiple of the mesh
            # size with duplicates of the last cell. Padded lanes are born
            # frozen (r_end=0), so they cost one loop-condition check per
            # chunk; their counters are never read.
            d = max(1, min(mode.devices, jax.local_device_count(), n))
            pad = (-n) % d
            self.nb = nb = n + pad
            self.batched = nb > 1
            if pad:
                ps = ps + [ps[-1]] * pad
                states = states + [states[-1]] * pad
            if self.batched:
                p = {k: np.stack([q[k] for q in ps]) for k in ps[0]}
                state = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *states
                )
            else:
                p, state = ps[0], states[0]
            if d > 1:
                self.mesh = sharding_policies.cell_mesh(d)
                shard = sharding_policies.cell_sharding
                p = jax.device_put(p, shard(self.mesh, p))
                state = jax.device_put(state, shard(self.mesh, state))
            else:
                self.mesh = None
                # commit the (possibly numpy-stacked) plan once so chunks
                # don't re-upload it every dispatch
                p = jax.device_put(p)
        self.p, self.state = p, state
        self.runner = None  # compiled lazily at first dispatch

        self._real = np.arange(nb) < n
        self.live = self._real.copy()
        self.warm = _zeros_like_counters(nb)
        self.warm_rounds = 0
        self.snaps: list[tuple | None] = [None] * n
        self.final: dict | None = None
        self.rounds_done = 0
        self.boundaries = chunk_boundaries(cfg0)
        self.pending: deque = deque()
        self.stopped = False
        self.exhausted = False
        self.started = False

    def start(self) -> None:
        """Dispatch the first chunk (compiling the runner if needed).

        :func:`run_cells` calls this on the *next* group while the
        current one executes, overlapping compile with execution.
        """
        if not self.started:
            self.started = True
            self._dispatch_one()

    def _dispatch_one(self) -> bool:
        if self.exhausted:
            return False
        b = next(self.boundaries, None)
        if b is None:
            self.exhausted = True
            return False
        # the first dispatch of a runner key compiles inside this span
        with jax.profiler.TraceAnnotation("repro.dispatch", round=b):
            if self.runner is None:
                self.runner = get_runner(
                    self.cfgs[0], self.meta, batched=self.batched,
                    mesh=self.mesh,
                )
            if self.batched:
                active = self.live if self.mode.early_exit else self._real
                r_arg = jnp.asarray(
                    np.where(active, b, 0).astype(np.int32)
                )
                if self.mesh is not None:
                    r_arg = jax.device_put(
                        r_arg,
                        sharding_policies.cell_sharding(self.mesh, r_arg),
                    )
            else:
                r_arg = jnp.asarray(b, jnp.int32)
            self.state = self.runner(self.p, self.state, r_arg)
            self.pending.append((b, _snapshot_counters(self.state)))
        return True

    def _resolve_one(self) -> None:
        b, snap = self.pending.popleft()
        with jax.profiler.TraceAnnotation("repro.sync"):
            host = _counters_to_host(snap, self.nb)
        self.rounds_done = b
        self.final = host
        if b <= self.cfgs[0].warmup_rounds:
            self.warm = host
            self.warm_rounds = b
        for i in range(self.n):
            if self.snaps[i] is None and (
                host["commits"][i] - self.warm["commits"][i]
                >= self.cfgs[i].target_commits
            ):
                self.snaps[i] = (
                    _cell_slice(host, i),
                    _cell_slice(self.warm, i),
                    b,
                    self.warm_rounds,
                )
                self.live[i] = False
        if all(sn is not None for sn in self.snaps):
            self.stopped = True

    def drive(self, prefetch=None) -> None:
        """Run the host loop to completion.

        At most ``mode.pipeline`` chunk boundaries stay unresolved in
        flight; ``prefetch`` (the next group's :meth:`start`) is invoked
        right after this group's first dispatch. Chunks dispatched past
        the stopping boundary are discarded unresolved — their lanes
        were already snapshotted from earlier boundary copies.
        """
        self.start()
        if prefetch is not None:
            prefetch()
        depth = max(0, self.mode.pipeline)
        while not self.stopped and not self.exhausted:
            while len(self.pending) > depth and not self.stopped:
                self._resolve_one()
            if not self.stopped:
                self._dispatch_one()
        while self.pending and not self.stopped:
            self._resolve_one()
        self.pending.clear()

    def finish(self, time_sink: dict | None = None) -> list[SimResult]:
        """Assemble per-cell :class:`SimResult`s (per-cell configs drive
        cost/arrival accounting; identical to the serial assembly).
        ``time_sink``, where given, receives ``group_cells``."""
        with jax.profiler.TraceAnnotation("repro.finish"):
            if self.final is None:
                self.final = _read_counters(self.state, self.nb)
            if time_sink is not None:
                time_sink["group_cells"] = self.n

            results = []
            for i in range(self.n):
                cfg = self.cfgs[i]
                cm = cfg.cost
                snap, wsnap, ri, wri = self.snaps[i] or (
                    _cell_slice(self.final, i),
                    _cell_slice(self.warm, i),
                    self.rounds_done,
                    self.warm_rounds,
                )
                commits = int(snap["commits"]) - int(wsnap["commits"])
                meas_rounds = ri - wri
                sim_seconds = meas_rounds * cm.round_seconds
                cat = snap["cat"].astype(np.int64) - wsnap["cat"].astype(
                    np.int64
                )
                total_lane_rounds = max(int(cat.sum()), 1)
                names = ["idle", "exec", "lock", "wait", "deadlock", "msg"]
                breakdown = {
                    nm: float(cat[k]) / total_lane_rounds
                    for k, nm in enumerate(names)
                }

                def _delta(k):
                    return int(np.asarray(snap.get(k, 0))) - int(
                        np.asarray(wsnap.get(k, 0))
                    )

                # goodput split (committed <= admitted <= offered): admitted
                # = arrival-stream consumption minus queue-side policy
                # drops; offered = the arrival schedule's output over the
                # measurement window. Open arrival only — closed-loop cells
                # keep offered=0 so their metrics rows (and cached benchmark
                # hashes) keep the pre-layer shape.
                rejected = _delta("pol_rejected")
                shed = _delta("pol_shed")
                admitted = _delta("next_txn") - rejected - shed
                if cfg.epoch_interval_rounds > 0:
                    offered = engine_lib.offered_by_round(
                        cfg, self.plans[i], ri
                    ) - engine_lib.offered_by_round(cfg, self.plans[i], wri)
                else:
                    offered = 0
                met = None
                if "lat_hist" in snap:
                    # histogram counters are cumulative (warmup-subtracted);
                    # queue samples are point-in-time (grid points past the
                    # capture round stay zero)
                    hist = snap["lat_hist"].astype(np.int64) - np.asarray(
                        wsnap.get("lat_hist", 0)
                    ).astype(np.int64)
                    qiv = engine_lib.qgrid_interval(cfg)
                    qgrid = (
                        np.arange(metrics_lib.QDEPTH_SAMPLES, dtype=np.int64)
                        + 1
                    ) * qiv
                    met = metrics_lib.build_metrics(
                        lat_hist=hist,
                        q_depth=snap["q_depth"],
                        q_inflight=snap["q_inflight"],
                        q_grid=qgrid,
                        breakdown=breakdown,
                        exec_lane_rounds=total_lane_rounds,
                        plan_busy_rounds=int(snap.get("plan_busy_int", 0))
                        - int(np.asarray(wsnap.get("plan_busy_int", 0))),
                        plan_lane_rounds=cfg.n_planner_lanes * meas_rounds,
                        committed=commits,
                        admitted=admitted,
                        offered=offered,
                        rejected=rejected,
                        shed=shed,
                        timedout=_delta("pol_timedout"),
                        sacrificed=_delta("pol_sacrificed"),
                    )
                results.append(
                    SimResult(
                        commits=commits,
                        aborts_deadlock=int(snap["aborts_dl"])
                        - int(wsnap["aborts_dl"]),
                        aborts_ollp=int(snap["aborts_ollp"])
                        - int(wsnap["aborts_ollp"]),
                        wasted_ops=int(snap["wasted"]) - int(wsnap["wasted"]),
                        rounds=meas_rounds,
                        sim_seconds=sim_seconds,
                        throughput_txn_s=commits / max(sim_seconds, 1e-12),
                        breakdown=breakdown,
                        raw=dict(
                            total_commits=int(snap["commits"]),
                            next_txn=int(snap["next_txn"]),
                            rounds_total=ri,
                            steps_executed=int(snap["steps"]),
                            group_cells=self.n,
                            engine_version=ENGINE_VERSION,
                            **{
                                k: int(snap[k])
                                - int(np.asarray(wsnap.get(k, 0)))
                                for k in _OPT_SCALARS
                                if k in snap
                            },
                        ),
                        metrics=met,
                    )
                )
            return results


def simulate_plans(
    cfg: EngineConfig,
    plans: list,
    mode: SweepMode | None = None,
) -> list[SimResult]:
    """Run one simulation per plan, sharing a single compiled runner.

    All plans must share a :class:`PlanMeta` (same shapes); a single plan
    runs unbatched, several run stacked under ``jax.vmap``. Per-cell
    counters are snapshotted at the chunk boundary where that cell meets
    ``target_commits`` — exactly where a serial run would have stopped —
    so every :class:`SweepMode` (sharded, pipelined, early-exit, or
    ``SERIAL_MODE``) produces identical :class:`SimResult`s.
    """
    if mode is None:
        mode = sweep_mode()
    run = _GroupRun([cfg] * len(plans), plans, mode)
    run.drive()
    return run.finish()


def _plan_shape_sig(p: dict) -> tuple:
    return tuple(
        sorted((k, tuple(v.shape), str(v.dtype)) for k, v in p.items())
    )


def run_cells(
    cells: list[tuple[EngineConfig, Workload]],
    mode: SweepMode | None = None,
) -> list[SimResult]:
    """Simulate many (config, workload) cells, sharing compilation.

    Cells are planned, grouped by compile key — shared
    ``trace_statics()``, host-loop budget, and plan shapes (configs may
    differ in traced values such as epoch rates or policy knobs) — and
    each group runs as one vmapped simulation under ``mode`` (default:
    :func:`sweep_mode` from the environment). Results come back in
    input order and are identical to calling
    :func:`engine_lib.run_simulation` per cell.
    """
    if mode is None:
        mode = sweep_mode()
    with jax.profiler.TraceAnnotation(
        "repro.run_cells", cells=len(cells)
    ) as span:
        plans = [engine_lib.make_plan(cfg, wl) for cfg, wl in cells]
        ps = []
        for (cfg, _wl), pl in zip(cells, plans):
            with jax.profiler.TraceAnnotation("repro.upload"):
                ps.append(engine_lib.plan_device(cfg, pl))
        groups: dict = {}
        for idx, ((cfg, _wl), plan, p) in enumerate(zip(cells, plans, ps)):
            key = (
                cfg.trace_statics(),
                (cfg.max_rounds, cfg.warmup_rounds, cfg.chunk_rounds,
                 cfg.target_commits),
                engine_lib.plan_meta(cfg, plan),
                _plan_shape_sig(p),
            )
            groups.setdefault(key, []).append(idx)
        span.set_metadata(groups=len(groups))

        order = list(groups.values())
        runs: list[_GroupRun | None] = [None] * len(order)

        def ensure(gi: int) -> _GroupRun:
            if runs[gi] is None:
                idxs = order[gi]
                runs[gi] = _GroupRun(
                    [cells[i][0] for i in idxs],
                    [plans[i] for i in idxs],
                    mode,
                    ps=[ps[i] for i in idxs],
                )
            return runs[gi]

        out: list = [None] * len(cells)
        for gi, idxs in enumerate(order):
            g = ensure(gi)
            prefetch = None
            if mode.pipeline > 0 and gi + 1 < len(order):
                # overlap the next group's compile + first dispatch with
                # this group's execution
                prefetch = lambda j=gi + 1: ensure(j).start()  # noqa: E731
            g.drive(prefetch)
            for idx, res in zip(idxs, g.finish()):
                out[idx] = res
            runs[gi] = None  # release state/plan buffers promptly
        return out
