"""The ORTHRUS transaction engine: eight protocols, one cycle-accounting core.

The simulator advances in rounds (``CostModel.cycles_per_round`` cycles). In
each round every lane interacts with the lock table at most once; waiting,
message latency, CC-lane saturation, coherence backlog on hot records,
deadlock handling and abort/retry all play out with exact protocol logic.

Protocol families — the planning spectrum (P2) crossed with functional
separation (P1):

  family            planning          locks   protocols
  ----------------- ----------------- ------- ---------------------------
  dynamic           none (program     yes     twopl_waitdie, twopl_waitfor,
                    order, inline)            twopl_dreadlocks
  per-txn planned   access set +      yes     deadlock_free (P2),
                    canonical order           orthrus (P1+P2),
                                              partitioned_store (coarse)
  batch planned     whole-batch       none    dgcc (conflict-graph
                    dependency                wavefronts), quecc (per-lane
                    graph / queues            execution queues)

Protocols (``EngineConfig.protocol``):
  twopl_waitdie | twopl_waitfor | twopl_dreadlocks
      dynamic 2PL: locks acquired in program order, interleaved with
      execution; deadlock handling per the named scheme.
  deadlock_free
      planned: canonical sorted order, all locks before execution (P2).
  orthrus
      planned + partitioned functionality: CC lanes own disjoint key
      partitions; exec lanes send request messages; CC_i forwards to
      CC_{i+1} (N_cc + 1 hops); exec lanes multiplex a window of
      outstanding transactions (P1 + P2).
  partitioned_store
      H-Store style: coarse partition locks, serial execution.
  dgcc | quecc
      batch planned (P1 + P2 at batch scope): planner lanes build, per
      batch-epoch, a transaction dependency schedule (DGCC: record-level
      conflict graph executed as wavefronts; QueCC: per-CC-lane
      totally-ordered execution queues). Execution never touches a lock
      table — a transaction starts when every planned predecessor has
      committed (the ``dep_wavefront`` primitive), so there is no
      deadlock handling, no abort path, and no coherence storm on record
      meta-data; the costs are batch planning (pipelined behind the
      previous batch) and per-dependency scheduler checks. Planning is
      charged either as a fixed pipelined *latency* (default), or —
      with ``EngineConfig.n_planner_lanes > 0`` — through the
      planner-lane *throughput* model: per-batch work scales with the
      batch's conflict-graph size, batches round-robin across planner
      lanes, and a batch's admission waits for its modeled
      plan-completion round (see ``repro.core.cost_model``). An epoch
      arrival rate (``EngineConfig.epoch_interval_rounds``) opens the
      system: input arrives over time instead of being fully queued at
      round 0, for every protocol family.

Execution model (this file + ``repro.core.sweep``):

  * The step builders take a static :class:`PlanMeta` (shapes only) and a
    dict of *traced* plan arrays, so one XLA compilation serves every cell
    of a figure sweep that shares (protocol statics, shapes). The compile
    cache and the vmapped multi-cell driver live in ``repro.core.sweep``.
  * **Event leaping** (``EngineConfig.event_leap``, on by default): each
    step computes the earliest future round at which any slot can act —
    the min over ``busy_until`` / ``msg_arrive`` / ``release_at`` /
    ``plan_fin`` timers, restricted to slots whose phase cannot act sooner
    — and advances ``r`` by the whole gap, scaling the lane-accounting
    increment by the leap width. Commits, aborts, round counts and the
    Fig-10 breakdown are bit-identical to the dense loop (property-tested
    in ``tests/test_engine_leap.py``). Round chunks therefore run as a
    ``lax.while_loop`` on the absolute round counter instead of a dense
    ``fori_loop``.
  * **Packed state matrix**: all per-slot scalar fields live in one
    field-major ``[SLOT_F, T]`` int32 matrix (see the ``C_*`` row
    constants below); saturated lock tables leap almost never, so their
    wall-clock is pure per-round step cost, and the packed layout plus a
    sort-free FIFO grant pass cut that roughly in half. The pre-rewrite
    step builders are frozen verbatim in ``repro.core.engine_legacy``
    and selectable via ``EngineConfig(state_layout="legacy")`` — the
    oracle for the differential conformance tests
    (``tests/test_golden_traces.py``, ``tests/test_engine_leap.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import planner as planner_lib
from repro.core.cost_model import (
    BACKOFF_SHIFT_CAP,
    DEFAULT_COST_MODEL,
    CostModel,
)
from repro.core.lockgrant import (
    KEY_SENTINEL,
    REQ_NONE,
    REQ_READ,
    REQ_RELEASE,
    REQ_WRITE,
    lex_order,
    segmented_grant,
    sort_requests,
    unsort,
)
from repro.core.metrics import LAT_BUCKETS, QDEPTH_SAMPLES
from repro.core.workloads import MODE_READ, MODE_WRITE, Workload

# Phases
EMPTY, INIT, ACQ, MSG, READY, EXEC, REL, BACKOFF = range(8)

# ---------------------------------------------------------------------------
# Packed state-matrix layout.
#
# Every per-slot scalar field lives in one int32 matrix ``state["slots"]``
# of shape [SLOT_F, T] — one named row (C_* constant) per field, one
# column per exec-lane slot; boolean fields are stored 0/1. This is the
# SoA packing of the logical [T, F] per-slot record: stored field-major
# so each field is a *contiguous* row (slot-major columns would make
# every unpack a strided slice, measurably slower on the CPU backend). A
# round unpacks the rows it needs into locals, runs ordinary column
# algebra, and repacks with a single ``jnp.stack``: XLA carries one
# buffer through the round loop instead of threading ~20 independent
# tiny [T] arrays through every masked update.
# [T, K] per-key masks and [R, ·] per-record state keep their own arrays.
(
    C_TID,         # loaded txn id (-1 = none)
    C_WIDX,        # workload index of the loaded txn
    C_LANE_CTR,    # H-Store per-lane stream cursor
    C_TS,          # timestamp (= txn id; unique per slot)
    C_PHASE,       # EMPTY .. BACKOFF
    C_COMMITTING,  # bool: REL path ends in commit (vs abort/backoff)
    C_BUSY_UNTIL,  # round until which the slot is busy
    C_BUSY_KIND,   # CAT_* charged while busy
    C_KPTR,        # next key index (program/canonical order)
    C_ATTEMPT,     # retry attempt counter
    C_CCPTR,       # ORTHRUS: first key of the current CC group
    C_MSG_ARRIVE,  # ORTHRUS/batch: message arrival round
    C_MSG_STAGE,   # ORTHRUS: 0 = acquire hop, 1 = response hop
    C_RELEASE_AT,  # round the release (message) lands
    C_WAITED,      # bool: slot was lock-waiting last round
    C_DL_DEBT,     # accumulated deadlock-handling cycles (mod round)
    C_ARRIVE,      # arrival round of the loaded txn (metrics: latency)
) = range(17)
SLOT_F = 17
SLOT_COLS = (
    "tid", "widx", "lane_ctr", "ts", "phase", "committing", "busy_until",
    "busy_kind", "kptr", "attempt", "ccptr", "msg_arrive", "msg_stage",
    "release_at", "waited", "dl_debt", "arrive",
)

# Batch-planned engine: a narrower [BATCH_SLOT_F, T] matrix (no lock
# table, no deadlock/retry state). BC_WIDX is the slot's *schedulable
# unit*: a workload txn index in whole-transaction mode, a fragment
# index under ``EngineConfig.fragment_exec``. BC_FTXN is the owning
# transaction either way (== BC_WIDX in txn mode) — the commit barrier
# joins a transaction's fragments through it.
(
    BC_TID,
    BC_WIDX,
    BC_TS,
    BC_PHASE,
    BC_BUSY_UNTIL,
    BC_BUSY_KIND,
    BC_MSG_ARRIVE,
    BC_FTXN,
    BC_ARRIVE,  # arrival round of the loaded unit's epoch (metrics)
) = range(9)
BATCH_SLOT_F = 9
BATCH_SLOT_COLS = (
    "tid", "widx", "ts", "phase", "busy_until", "busy_kind", "msg_arrive",
    "ftxn", "arrive",
)


def slot_col(state: dict, col: int):
    """Read one packed slot-matrix field (int32 [T]) from a state dict."""
    return state["slots"][col]


def slot_col_bool(state: dict, col: int):
    """Read a 0/1 slot-matrix field as bool [T]."""
    return state["slots"][col] != 0

# Sharer-heat epoch length (rounds) for the coherence model: roughly how
# long a hot line's sharer population stays cache-resident (~1 ms).
EPOCH_BITS = 12
# Lane-time categories (paper Fig 10 breakdown)
CAT_IDLE, CAT_EXEC, CAT_LOCK, CAT_WAIT, CAT_DL, CAT_MSG = range(6)
NCAT = 6

_IMAX = jnp.iinfo(jnp.int32).max

# Saturation bound for the open-arrival closed forms: products of
# (txn-id, round) quantities clamp here instead of wrapping int32. 2^30
# is beyond any simulable round or txn id, so a saturated arrival round
# reads as "never arrives" and a saturated count as "everything" — both
# safe, and the clamp never fires inside the int32-exact range, so
# results there are bit-identical to the unguarded arithmetic.
_SAT = 1 << 30


def _sat_mul(a, b):
    """``a * b`` clamped to ``_SAT`` (int32-safe; a >= 0, b >= 0)."""
    return jnp.where(a > _SAT // jnp.maximum(b, 1), _SAT, a * b)

PROTOCOLS = (
    "twopl_waitdie",
    "twopl_waitfor",
    "twopl_dreadlocks",
    "deadlock_free",
    "orthrus",
    "partitioned_store",
    "dgcc",
    "quecc",
    "scheduled",
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    protocol: str
    n_exec: int  # execution lanes (= all DB threads for shared protocols)
    n_cc: int = 0  # ORTHRUS concurrency-control lanes
    window: int = 1  # outstanding txns per exec lane (ORTHRUS asynchrony)
    # SPLIT ORTHRUS / Split Deadlock-free (paper §4.3): indexes physically
    # partitioned across worker threads -> no shared-index cache penalty.
    split_index: bool = False
    # Event leaping: advance r straight to the next-event round instead of
    # stepping every dense round. Simulated results are identical either
    # way; False forces the dense reference loop (used by the equivalence
    # property tests).
    event_leap: bool = True
    # State layout: "packed" = the [SLOT_F, T] slot-matrix engine (this
    # file — the SoA packing of the logical [T, F] per-slot record);
    # "legacy" = the frozen pre-rewrite dict-of-[T]-arrays step builders
    # (repro.core.engine_legacy), kept only as the bit-exactness oracle
    # for the differential conformance tests. Results are identical.
    state_layout: str = "packed"
    # Fragment-granular batch execution (dgcc / quecc only): schedule
    # per-(txn, lane) *fragments* instead of whole transactions; a txn
    # commits when all its fragments are done (QueCC's execution model).
    # Off by default — txn-granular results are bit-identical to the
    # pre-fragment engine (golden-trace enforced).
    fragment_exec: bool = False
    # Inter-batch pipelined admission (DGCC §5), requires fragment_exec:
    # level-0 fragments of batch b+1 become admission-eligible while
    # batch b drains (once b+1's plan is ready), instead of waiting for
    # the full batch barrier.
    inter_batch_pipeline: bool = False
    # Planner-lane throughput model (dgcc / quecc): 0 (default) keeps the
    # fixed pipelined-latency planning charge; L > 0 models L planner
    # lanes with per-batch work that scales with the batch's
    # conflict-graph size (txns, key-ops, edges, fragments — see
    # CostModel.planner_batch_cycles). Batch g is planned end-to-end by
    # lane g % L; plans queue behind busy lanes, and a batch's admission
    # gates on its modeled plan-completion round.
    n_planner_lanes: int = 0
    # Epoch arrival interval (rounds): batch/epoch g's transactions
    # arrive at round g * epoch_interval_rounds (an open system). 0
    # (default) = the whole input is queued at round 0 (closed loop).
    # For non-batch protocols, epochs are batch_epoch-sized slices of
    # the workload's submission order.
    epoch_interval_rounds: int = 0
    # --- overload robustness layer (all defaults = off; the off paths
    # compile to the pre-layer graph, so golden traces stay
    # bit-identical) ---
    # Admission-control policy over the open-arrival backlog. The
    # *kind* is a compile-time static (each policy gates admission with
    # different traced arithmetic); every numeric parameter below is a
    # traced plan scalar, so one compiled runner serves a whole policy
    # sweep. Requires open arrival (epoch_interval_rounds > 0).
    #   none            unbounded backlog (the pre-layer behavior)
    #   bounded_backlog drop the oldest waiters whenever the backlog
    #                   exceeds backlog_cap (counted in pol_rejected)
    #   token_bucket    admission additionally waits for a token: the
    #                   bucket holds token_burst tokens and refills one
    #                   every token_interval_rounds (backpressure — no
    #                   drops; admissions counted in pol_tb_adm)
    #   deadline_shed   drop waiters whose queueing delay exceeds
    #                   deadline_rounds (pol_shed), and give up on
    #                   admitted txns that abort past the end-to-end
    #                   deadline (pol_timedout)
    admission_policy: str = "none"
    backlog_cap: int = 0  # bounded_backlog: max waiting txns
    token_interval_rounds: int = 0  # token_bucket: rounds per token
    token_burst: int = 0  # token_bucket: bucket capacity
    deadline_rounds: int = 0  # deadline_shed: deadline (rounds)
    # Bounded retry: after retry_budget total attempts an aborted txn is
    # dropped instead of backing off again (counted in pol_sacrificed).
    # 0 = unlimited retries (default). The budget value is traced; only
    # the on/off flag is static.
    retry_budget: int = 0
    # Abort backoff: "fixed" = cost.abort_backoff_rounds every time
    # (the pre-layer behavior); "exp" = bounded exponential,
    # min(base << min(attempt, 16), backoff_max_rounds) — deterministic
    # shift-and-cap integer math on the C_ATTEMPT column, exact under
    # event leaping and vmapping (cost_model.exp_backoff_rounds is the
    # host-side oracle).
    backoff_mode: str = "fixed"
    backoff_max_rounds: int = 256  # exp backoff cap (traced)
    # Bursty open arrival: replace the fixed epoch interval with a
    # deterministic schedule (workloads.epoch_arrival_schedule) —
    # "burst" = on/off (all of burst_period_epochs' epochs arrive
    # within the first burst_on_epochs intervals), "diurnal" = square
    # wave (first half of the period at double rate). Average offered
    # load matches the uniform schedule; arrival rounds are stamped
    # per-txn so event leaping wakes exactly at bursts.
    arrival_pattern: str = "uniform"
    burst_period_epochs: int = 0
    burst_on_epochs: int = 0
    # K-round mega-dispatch: the sweep runner unrolls K copies of the
    # step body per `lax.while_loop` iteration (each copy guarded by
    # `r < r_end`), amortizing the fixed per-op dispatch overhead of
    # ~90 fused kernels/round across K rounds. Results are bit-identical
    # for every K — each executed inner step sees exactly the state the
    # K=1 loop would have seen, and steps at the chunk bound are skipped
    # — so golden traces and differential oracles hold at any value.
    # The compiled value is the pow2 bucket `dispatch_rounds` (trace
    # static), so a K sweep shares runners per bucket.
    rounds_per_dispatch: int = 1
    # Release / wait-for representation for the non-ORTHRUS grant +
    # deadlock stages. "csr" (default): FIFO enq-min via a [T]-sized
    # sort + segmented min over the compact pending-request list, and
    # wait-for edges from the lock table (write holders) plus a carried
    # per-record packed reader bitmask — no [T, T] stamp comparison and
    # no [T, T, K] key-equality tensor. "dense": the all-pairs
    # formulation, kept as the in-tree oracle (results are bit-identical
    # — golden traces run the csr path, differential tests compare the
    # two). Ignored by ORTHRUS (segmented-grant path) and the batch
    # engines; the frozen legacy layout predates the flag.
    release_path: str = "csr"
    # Grant/wavefront inner-loop implementation: "jnp" = the pure-jnp
    # formulations in this file; "pallas" = the Pallas kernels
    # (kernels.lock_grant for the ORTHRUS segmented grant,
    # kernels.dep_wavefront for the batch-engine readiness scan),
    # interpret-or-compiled per kernels.resolve_interpret; "auto"
    # (default) = pallas on backends with compiled Pallas (TPU/GPU),
    # jnp elsewhere. Both paths are bit-identical (the jnp code is the
    # kernels' oracle).
    kernel_impl: str = "auto"
    max_rounds: int = 60_000
    warmup_rounds: int = 4_000
    chunk_rounds: int = 4_000
    target_commits: int = 50_000
    cost: CostModel = DEFAULT_COST_MODEL

    def __post_init__(self):
        assert self.protocol in PROTOCOLS, self.protocol
        assert self.state_layout in ("packed", "legacy"), self.state_layout
        if self.protocol == "orthrus":
            assert self.n_cc >= 1
        if self.protocol == "quecc":
            assert self.n_cc >= 1, "quecc needs n_cc planner/queue lanes"
        if self.protocol == "scheduled":
            assert self.state_layout == "packed", (
                "the frozen legacy engine predates the scheduled family"
            )
        if self.fragment_exec or self.inter_batch_pipeline:
            assert self.protocol in ("dgcc", "quecc"), (
                "fragment execution / inter-batch pipelining are "
                "batch-planned (dgcc/quecc) features; the scheduled "
                "family's clusters are txn-granular"
            )
            assert self.state_layout == "packed", (
                "the frozen legacy engine predates fragment execution"
            )
        if self.inter_batch_pipeline:
            assert self.fragment_exec, (
                "inter-batch pipelining admits level-0 *fragments*: "
                "enable fragment_exec"
            )
        assert self.n_planner_lanes >= 0
        assert self.epoch_interval_rounds >= 0
        if self.n_planner_lanes:
            assert self.is_batch_planned, (
                "the planner-lane throughput model charges *batch* "
                "planning/scheduling: it applies to dgcc/quecc/"
                "scheduled only"
            )
        if self.n_planner_lanes or self.epoch_interval_rounds:
            assert self.state_layout == "packed", (
                "the frozen legacy engine predates the planner-lane "
                "model and open epoch arrival"
            )
        if self.epoch_interval_rounds:
            assert self.protocol != "partitioned_store", (
                "open epoch arrival is not modeled for the H-Store "
                "per-lane admission streams"
            )
        # --- overload robustness layer ---
        assert self.admission_policy in (
            "none", "bounded_backlog", "token_bucket", "deadline_shed"
        ), self.admission_policy
        assert self.backoff_mode in ("fixed", "exp"), self.backoff_mode
        assert self.arrival_pattern in (
            "uniform", "burst", "diurnal"
        ), self.arrival_pattern
        assert self.retry_budget >= 0
        if self.admission_policy != "none":
            assert self.epoch_interval_rounds > 0, (
                "admission policies gate the open-arrival backlog: "
                "set epoch_interval_rounds"
            )
            assert not self.inter_batch_pipeline, (
                "admission policies skip whole epochs at batch "
                "rollover, which the pipelined level-0 cursor does "
                "not model"
            )
            if self.admission_policy == "bounded_backlog":
                assert self.backlog_cap > 0
            if self.admission_policy == "token_bucket":
                assert self.token_interval_rounds > 0
                assert self.token_burst > 0
            if self.admission_policy == "deadline_shed":
                assert self.deadline_rounds > 0
        if self.retry_budget or self.backoff_mode != "fixed":
            assert not self.is_batch_planned, (
                "batch-planned execution has no abort path: retry "
                "budgets and backoff shaping do not apply"
            )
        if self.arrival_pattern != "uniform":
            assert self.epoch_interval_rounds > 0, (
                "bursty arrival shapes the open-arrival schedule: "
                "set epoch_interval_rounds"
            )
            assert self.burst_period_epochs > 0
            if self.arrival_pattern == "burst":
                assert 0 < self.burst_on_epochs <= self.burst_period_epochs
        if (
            self.admission_policy != "none"
            or self.retry_budget
            or self.backoff_mode != "fixed"
            or self.arrival_pattern != "uniform"
        ):
            assert self.state_layout == "packed", (
                "the frozen legacy engine predates the overload "
                "robustness layer"
            )
        assert self.rounds_per_dispatch >= 1, self.rounds_per_dispatch
        assert self.release_path in ("csr", "dense"), self.release_path
        assert self.kernel_impl in ("auto", "jnp", "pallas"), self.kernel_impl
        if self.release_path != "csr" or self.kernel_impl != "auto":
            assert self.state_layout == "packed", (
                "the frozen legacy engine has a single (dense, jnp) "
                "grant/wait-for formulation"
            )

    @property
    def n_slots(self) -> int:
        return self.n_exec * self.window

    @property
    def is_orthrus(self) -> bool:
        return self.protocol == "orthrus"

    @property
    def is_batch_planned(self) -> bool:
        """Protocols that execute a precomputed batch schedule through
        ``make_batch_step`` (no lock table, no abort path). The
        `scheduled` family qualifies: its cluster chains are just a
        degenerate dependency schedule (in-degree <= 1), so it rides
        the whole batch path — plan gating, open arrival, planner
        lanes, metrics, leaping — for free."""
        return self.protocol in ("dgcc", "quecc", "scheduled")

    @property
    def dispatch_rounds(self) -> int:
        """Compiled steps per XLA dispatch: ``rounds_per_dispatch``
        rounded up to a power of two, so a K sweep shares compiled
        runners per bucket instead of compiling one program per K."""
        return 1 << (self.rounds_per_dispatch - 1).bit_length()

    @property
    def is_dynamic_2pl(self) -> bool:
        return self.protocol.startswith("twopl")

    @property
    def deadlock_scheme(self) -> str:
        return {
            "twopl_waitdie": "waitdie",
            "twopl_waitfor": "waitfor",
            "twopl_dreadlocks": "dreadlocks",
        }.get(self.protocol, "none")

    def trace_statics(self) -> tuple:
        """The config fields the traced step computation depends on.

        Chunk length and termination targets are host-loop concerns (the
        chunk end is a traced argument), so two cells differing only in
        simulation budget share one compilation.
        """
        return (
            self.protocol,
            self.n_exec,
            self.n_cc,
            self.window,
            self.split_index,
            self.event_leap,
            self.state_layout,
            self.fragment_exec,
            self.inter_batch_pipeline,
            # the planner-lane count shapes the carried lane_free state;
            # the epoch *interval* is a traced scalar (one compilation
            # serves a whole epoch-rate sweep) — only open vs closed
            # arrival changes the traced computation
            self.n_planner_lanes,
            self.epoch_interval_rounds > 0,
            # overload robustness: policy / backoff / burst *kinds* are
            # static (each compiles different gating arithmetic); their
            # numeric parameters are traced plan scalars, so one runner
            # serves a whole policy-parameter sweep
            self.admission_policy,
            self.retry_budget > 0,
            self.backoff_mode,
            self.arrival_pattern != "uniform",
            # mega-dispatch: only the pow2 bucket is compiled in, so
            # e.g. rounds_per_dispatch 5..8 share one runner
            self.dispatch_rounds,
            self.release_path,
            self.kernel_impl,
            self.cost,
        )


@dataclasses.dataclass(frozen=True)
class PlanMeta:
    """Static (shape-only) description of a plan: everything ``make_step``
    bakes into the compiled computation. Plans sharing a ``PlanMeta`` (and
    ``EngineConfig.trace_statics``) share one XLA compilation; the actual
    plan arrays are traced arguments."""

    n_txns: int  # N
    max_keys: int  # K
    num_records: int  # R, padded to a pow2 bucket by _compact_keys
    lane_cols: int = 0  # H-Store lane_stream width; 0 = absent
    pred_width: int = 0  # batch schedule: pred_pad columns
    num_batches: int = 0  # batch schedule: NB
    n_frags: int = 0  # fragment mode: total fragments F
    frag_pred_width: int = 0  # fragment mode: frag_pred_pad columns


@dataclasses.dataclass
class SimResult:
    commits: int
    aborts_deadlock: int
    aborts_ollp: int
    wasted_ops: int
    rounds: int
    sim_seconds: float
    throughput_txn_s: float
    breakdown: dict[str, float]  # exec-lane time fractions
    raw: dict[str, Any]
    # structured metrics record (repro.core.metrics.Metrics): latency
    # histogram + percentiles, queue trajectories, extended breakdown.
    # None for the legacy-layout oracle engine, which predates the
    # metrics state.
    metrics: Any = None


def plan_meta(cfg: EngineConfig, plan: planner_lib.Plan) -> PlanMeta:
    """Shape signature of a plan for the compile cache / vmap grouping."""
    if cfg.is_batch_planned:
        sched = plan.sched
        assert sched is not None, "batch protocols require a planned schedule"
        frag_kw = {}
        if cfg.fragment_exec:
            frag_kw = dict(
                n_frags=sched.n_frags,
                frag_pred_width=sched.frag_pred_pad.shape[1],
            )
        return PlanMeta(
            n_txns=sched.n_txns,
            max_keys=plan.keys.shape[1],
            num_records=plan.num_records,
            pred_width=plan.sched.pred_pad.shape[1],
            num_batches=sched.num_batches,
            **frag_kw,
        )
    return PlanMeta(
        n_txns=plan.keys.shape[0],
        max_keys=plan.keys.shape[1],
        num_records=plan.num_records,
        lane_cols=0 if plan.lane_stream is None else plan.lane_stream.shape[1],
    )


def qgrid_interval(cfg: EngineConfig) -> int:
    """Round spacing of the queue-depth sample grid: QDEPTH_SAMPLES
    points cover (0, max_rounds] for any budget. The spacing is a
    traced plan scalar, so cells differing only in round budget share
    one compiled runner and one [QDEPTH_SAMPLES] state shape."""
    return max(1, -(-cfg.max_rounds // QDEPTH_SAMPLES))


def _epoch_schedule_arrays(cfg: EngineConfig) -> tuple[np.ndarray, int, int]:
    """One period of the bursty epoch-arrival schedule:
    ``(sched [SP], period_rounds, SP)`` (see
    ``workloads.epoch_arrival_schedule``). Only meaningful when
    ``cfg.arrival_pattern != "uniform"``."""
    from repro.core.workloads import epoch_arrival_schedule

    sched, period = epoch_arrival_schedule(
        cfg.arrival_pattern,
        cfg.epoch_interval_rounds,
        cfg.burst_period_epochs,
        cfg.burst_on_epochs,
    )
    return sched.astype(np.int64), int(period), len(sched)


def _policy_scalars(cfg: EngineConfig) -> dict:
    """Traced scalar parameters of the overload-robustness layer. Only
    the parameters of the *active* policy are emitted, so default
    configs carry no extra plan entries and cells sweeping a policy
    parameter share one compiled runner."""
    p: dict = {}
    i32 = np.int32
    if cfg.admission_policy == "bounded_backlog":
        p["pol_cap"] = np.asarray(cfg.backlog_cap, i32)
    elif cfg.admission_policy == "token_bucket":
        p["pol_tb_iv"] = np.asarray(cfg.token_interval_rounds, i32)
        p["pol_tb_burst"] = np.asarray(cfg.token_burst, i32)
    elif cfg.admission_policy == "deadline_shed":
        p["pol_deadline"] = np.asarray(cfg.deadline_rounds, i32)
    if cfg.retry_budget > 0:
        p["pol_retry_budget"] = np.asarray(cfg.retry_budget, i32)
    if cfg.backoff_mode == "exp":
        p["pol_bo_max"] = np.asarray(cfg.backoff_max_rounds, i32)
    return p


def plan_device(cfg: EngineConfig, plan: planner_lib.Plan) -> dict:
    """The traced plan arrays consumed by the step builders.

    The packed engine reads fused per-txn scalar matrices
    (``txn_scalars`` [N, 4]; batch: ``txn_ne`` [N, 2]) so each round
    gathers one matrix row per slot instead of one gather per scalar
    field; the legacy oracle reads the individual arrays. Both views are
    emitted — jit drops whichever set the selected step builder leaves
    unused. (The [N, K] key/mode/part arrays stay separate: fusing them
    into an [N, K, 3] tensor makes every downstream use a strided slice,
    which measured slower than three contiguous gathers.)
    """
    if cfg.is_batch_planned:
        sched = plan.sched
        npred = np.asarray(sched.npred, np.int32)
        exec_ops = np.asarray(plan.exec_ops, np.int32)
        p = dict(
            exec_ops=exec_ops,
            npred=npred,
            txn_ne=np.stack([npred, exec_ops], axis=1),
            pred_pad=np.asarray(sched.pred_pad, np.int32),
            batch_of=np.asarray(sched.batch_of, np.int32),
            batch_start=np.asarray(sched.batch_start, np.int32),
            batch_size=np.asarray(sched.batch_size, np.int32),
            plan_rounds=_batch_plan_rounds(cfg, plan),
        )
        if cfg.fragment_exec:
            # per-fragment executable ops: the fragment's own key-ops,
            # plus the txn's non-keyed ops (e.g. TPC-C Item reads) on
            # the fragment holding the txn's first planned key
            frag_txn = np.asarray(sched.frag_txn, np.int64)
            extra = (exec_ops - np.asarray(plan.nkeys, np.int32))[frag_txn]
            frag_exec = np.asarray(sched.frag_nkeys, np.int32) + np.where(
                sched.frag_first, np.maximum(extra, 0), 0
            ).astype(np.int32)
            frag_npred = np.asarray(sched.frag_npred, np.int32)
            p.update(
                frag_ne=np.stack([frag_npred, frag_exec], axis=1),
                frag_pred_pad=np.asarray(sched.frag_pred_pad, np.int32),
                frag_txn=frag_txn.astype(np.int32),
                frag_batch=np.asarray(
                    sched.batch_of[frag_txn], np.int32
                ),
                txn_nfrags=np.asarray(sched.txn_nfrags, np.int32),
                batch_fstart=np.asarray(sched.batch_fstart, np.int32),
                batch_fsize=np.asarray(sched.batch_fsize, np.int32),
                lvl0_fcount=np.asarray(sched.lvl0_fcount, np.int32),
            )
        if cfg.n_planner_lanes > 0:
            p["plan_work"] = _planner_work_rounds(cfg, plan)
        if cfg.n_planner_lanes > 0 or cfg.epoch_interval_rounds > 0:
            # traced scalar: every epoch-rate point of a sweep shares
            # one compiled runner (see EngineConfig.trace_statics)
            p["epoch_interval"] = np.asarray(
                cfg.epoch_interval_rounds, np.int32
            )
        if cfg.epoch_interval_rounds > 0:
            # cumulative batch sizes in admission units (fragments under
            # fragment_exec): closed-form arrived-unit counts at any
            # round for the backlog samples (epoch g arrives whole at
            # round g * interval; the workload wraps modulo NB)
            usz = (
                sched.batch_fsize if cfg.fragment_exec
                else sched.batch_size
            )
            p["cum_usize"] = np.concatenate(
                [[0], np.cumsum(usz)]
            ).astype(np.int32)
        if cfg.arrival_pattern != "uniform":
            sched_arr, period, sp = _epoch_schedule_arrays(cfg)
            p["ep_sched"] = sched_arr.astype(np.int32)
            p["sched_period"] = np.asarray(period, np.int32)
            p["sched_epochs"] = np.asarray(sp, np.int32)
        p.update(_policy_scalars(cfg))
        if cfg.admission_policy in ("bounded_backlog", "token_bucket"):
            # the batch engine sheds / gates whole epochs: caps given in
            # transactions round down to epochs (at least one)
            b = max(int(plan.epoch_txns), 1)
            if cfg.admission_policy == "bounded_backlog":
                p["pol_cap_epochs"] = np.asarray(
                    max(cfg.backlog_cap // b, 1), np.int32
                )
            else:
                p["pol_tb_burst_e"] = np.asarray(
                    max(cfg.token_burst // b, 1), np.int32
                )
        p["qgrid_iv"] = np.asarray(qgrid_interval(cfg), np.int32)
        return p
    keys = np.asarray(plan.keys, np.int32)
    modes = np.asarray(plan.modes, np.int32)
    part = np.asarray(plan.part, np.int32)
    nkeys = np.asarray(plan.nkeys, np.int32)
    exec_ops = np.asarray(plan.exec_ops, np.int32)
    ollp = np.asarray(plan.ollp, bool)
    ollp_miss = np.asarray(plan.ollp_miss, bool)
    p = dict(
        keys=keys,
        modes=modes,
        part=part,
        nkeys=nkeys,
        exec_ops=exec_ops,
        ollp=ollp,
        ollp_miss=ollp_miss,
        txn_scalars=np.stack(
            [nkeys, exec_ops, ollp.astype(np.int32),
             ollp_miss.astype(np.int32)], axis=1
        ),
    )
    if plan.lane_stream is not None:
        p["lane_stream"] = np.asarray(plan.lane_stream, np.int32)
    if cfg.epoch_interval_rounds > 0:
        # open arrival: txn i of the workload arrives with its epoch
        # (epoch-sized slices of submission order); the workload wraps
        # modulo N, so the engine adds (g // N) * arrive_cycle for
        # global txn id g.
        n = keys.shape[0]
        b = max(int(plan.epoch_txns), 1)
        iv = int(cfg.epoch_interval_rounds)
        n_ep = -(-n // b)
        if cfg.arrival_pattern != "uniform":
            # bursty arrival: epoch e's round comes from the periodic
            # schedule (tiled across the workload's epochs); admission,
            # leaping and latency stamping all read arrive_round, so
            # only the backlog closed form needs the per-epoch array
            sched_arr, period, sp = _epoch_schedule_arrays(cfg)
            reps = -(-n_ep // sp)
            ep_arr = (
                np.tile(sched_arr, reps)
                + np.repeat(np.arange(reps, dtype=np.int64) * period, sp)
            )[:n_ep]
            p["arrive_round"] = ep_arr[
                np.arange(n, dtype=np.int64) // b
            ].astype(np.int32)
            p["arrive_cycle"] = np.asarray(reps * period, np.int32)
            p["ep_arrive"] = ep_arr.astype(np.int32)
        else:
            p["arrive_round"] = (
                (np.arange(n, dtype=np.int64) // b) * iv
            ).astype(np.int32)
            p["arrive_cycle"] = np.asarray(n_ep * iv, np.int32)
        # epoch size / interval as traced scalars: closed-form
        # arrived-txn counts at any round for the backlog samples
        p["epoch_txns"] = np.asarray(b, np.int32)
        p["epoch_interval"] = np.asarray(iv, np.int32)
        p.update(_policy_scalars(cfg))
    elif cfg.backoff_mode == "exp" or cfg.retry_budget > 0:
        # backoff shaping / retry budgets apply under closed loop too
        p.update(_policy_scalars(cfg))
    p["qgrid_iv"] = np.asarray(qgrid_interval(cfg), np.int32)
    return p


def offered_by_round(
    cfg: EngineConfig, plan: planner_lib.Plan, r: int
) -> int:
    """Host-side mirror of the engine's arrived-by closed form: how
    many schedulable units (txns; fragments under ``fragment_exec``)
    the open-arrival schedule has offered by round ``r`` inclusive.
    Exact int64 arithmetic — the goodput denominator for
    ``Metrics``' committed / admitted / offered split. Returns 0 for
    closed-loop configs (offered == admitted there)."""
    if cfg.epoch_interval_rounds <= 0 or r < 0:
        return 0
    iv = int(cfg.epoch_interval_rounds)
    if cfg.is_batch_planned:
        sched = plan.sched
        nb = sched.num_batches
        usz = sched.batch_fsize if cfg.fragment_exec else sched.batch_size
        cum = np.concatenate([[0], np.cumsum(np.asarray(usz, np.int64))])
        nu = int(cum[-1])
        if cfg.arrival_pattern != "uniform":
            ep_sched, period, sp = _epoch_schedule_arrays(cfg)
            n_arr = (r // period) * sp + int(
                np.searchsorted(ep_sched, r % period, side="right")
            )
        else:
            n_arr = r // iv + 1
        return int((n_arr // nb) * nu + cum[n_arr % nb])
    n = int(plan.keys.shape[0])
    b = max(int(plan.epoch_txns), 1)
    n_ep = -(-n // b)
    if cfg.arrival_pattern != "uniform":
        ep_sched, period, sp = _epoch_schedule_arrays(cfg)
        reps = -(-n_ep // sp)
        ep_arr = (
            np.tile(ep_sched, reps)
            + np.repeat(np.arange(reps, dtype=np.int64) * period, sp)
        )[:n_ep]
        cyc = reps * period
        in_cyc = int(
            np.searchsorted(ep_arr, r % cyc, side="right")
        ) * b
    else:
        cyc = n_ep * iv
        in_cyc = (r % cyc // iv + 1) * b
    return int((r // cyc) * n + min(in_cyc, n))


def _use_pallas(cfg: EngineConfig) -> bool:
    """Whether the step builders call the Pallas kernels for the grant /
    wavefront inner loops (``EngineConfig.kernel_impl``). "auto" picks
    pallas only where compiled Pallas exists (TPU/GPU) — on CPU the
    kernels would run the interpreter, orders of magnitude slower than
    the jnp formulations they are bit-identical to."""
    if cfg.kernel_impl == "auto":
        return jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")
    return cfg.kernel_impl == "pallas"


def rebase_enq(s: dict) -> dict:
    """Rebase enqueue stamps against the minimum live stamp.

    ``enq_ctr`` is a monotone int32: at saturation (new request +
    release stamps every round) it wraps after a few million rounds and
    silently corrupts the FIFO enq-min grant comparison, whose strict
    compares assume stamps never decrease. Grant decisions depend only
    on stamp *differences* among live entries (``want | granted``), so
    subtracting a uniform delta that pins the minimum live stamp at 1
    is bit-exact — the sweep runner applies this at every dispatch
    boundary, bounding the counter by the in-flight request count
    instead of total simulated work. With no live entries the counter
    resets to 1. (The frozen legacy oracle keeps the unrebased counter;
    decisions are equal over any horizon short of the wrap.)
    """
    live = s["want"] | s["granted"]
    m = jnp.min(jnp.where(live, s["enq"], _IMAX))
    delta = jnp.minimum(m, s["enq_ctr"]) - 1
    s = dict(s)
    s["enq"] = s["enq"] - delta
    s["enq_ctr"] = s["enq_ctr"] - delta
    return s


def _state0(cfg: EngineConfig, num_records: int, T: int, K: int):
    R = num_records
    i32 = jnp.int32
    s = dict(
        r=jnp.zeros((), i32),
        next_txn=jnp.zeros((), i32),
        enq_ctr=jnp.ones((), i32),
        # all per-slot scalar fields: one [SLOT_F, T] matrix (see C_*)
        slots=jnp.zeros((SLOT_F, T), i32).at[C_TID].set(-1),
        want=jnp.zeros((T, K), jnp.bool_),
        granted=jnp.zeros((T, K), jnp.bool_),
        enq=jnp.zeros((T, K), i32),
        adm_done=jnp.zeros((T, K), jnp.bool_),
        rel_done=jnp.zeros((T, K), jnp.bool_),
        reach=jnp.zeros((T, T), jnp.bool_),
        wh=jnp.full((R,), -1, i32),
        rc=jnp.zeros((R,), i32),
        commits=jnp.zeros((), i32),
        aborts_dl=jnp.zeros((), i32),
        aborts_ollp=jnp.zeros((), i32),
        wasted=jnp.zeros((), i32),
        cat=jnp.zeros((NCAT,), jnp.int32),
        steps=jnp.zeros((), i32),
        # metrics: log-bucketed commit-latency histogram + queue-depth
        # samples on the fixed round grid (see repro.core.metrics)
        lat_hist=jnp.zeros((LAT_BUCKETS,), i32),
        q_depth=jnp.zeros((QDEPTH_SAMPLES,), i32),
        q_inflight=jnp.zeros((QDEPTH_SAMPLES,), i32),
    )
    # Per-record side state is held as 1-D [R] columns, one per field,
    # like wh and rc: a row scatter or gather into an [R, F] array with a
    # small minor dimension costs several times a 1-D one on the TPU
    # (the minor dimension pads to 128 lanes, which puts the array in
    # HBM, and each row is a serial update there), and a 2-D array read
    # by one stage in another layout than the one it is scattered in
    # gets a whole-array relayout copy every round.
    if cfg.protocol != "orthrus":
        # stage 9's cost model: sharer heat (epoch, count in the current
        # epoch, count in the previous one) and the meta-data line
        # (line-free round, last lane to touch it)
        s["heat"] = (jnp.full((R,), -10, i32), jnp.zeros((R,), i32),
                     jnp.zeros((R,), i32))
        s["line"] = (jnp.zeros((R,), i32), jnp.full((R,), -1, i32))
        # carried per-record same-round contention sums (active entries,
        # new ops, queue mutations; see stage 9 of make_step): a single
        # scatter-add per column and round removes the previous round's
        # contributions (agg_prev_*) and applies the current ones, so
        # each column is mutated once and only *then* read — XLA
        # aliases it in place. (Any formulation that gathers a column
        # both before and after its scatter makes copy insertion
        # duplicate the whole [R] column every round.)
        s["agg_sum"] = tuple(jnp.zeros((R,), i32) for _ in range(3))
        s["agg_prev_idx"] = jnp.full((T, K), R, i32)
        s["agg_prev_upd"] = tuple(jnp.zeros((T, K), i32) for _ in range(3))
    if cfg.release_path == "csr" and cfg.deadlock_scheme != "none":
        # csr wait-for: carried per-record reader bitmask, one [R] column
        # per 32 slots (bit u % 32 of rdr[u // 32][q] = slot u holds >= 1
        # granted read entry on record q), maintained incrementally at
        # grant / release — the deadlock stage gathers waiters' digests
        # from it instead of building the dense [T, T, K] key-equality
        # tensor.
        s["rdr"] = tuple(jnp.zeros((R,), i32) for _ in range((T + 31) // 32))
    # overload-robustness counters (carried scalars; sweep._OPT_SCALARS
    # picks up whichever are present). Keyed on the same statics as the
    # step builder, so vmapped cells always share a state shape.
    if cfg.admission_policy != "none":
        s["pol_rejected"] = jnp.zeros((), i32)  # bounded_backlog drops
        s["pol_shed"] = jnp.zeros((), i32)  # deadline_shed queue drops
        s["pol_timedout"] = jnp.zeros((), i32)  # in-flight deadline hits
        s["pol_tb_adm"] = jnp.zeros((), i32)  # token-bucket admissions
    if cfg.retry_budget > 0:
        s["pol_sacrificed"] = jnp.zeros((), i32)  # retry budget exhausted
    if cfg.backoff_mode == "exp":
        s["pol_backoff_rounds"] = jnp.zeros((), i32)  # total backoff issued
    return s


def make_step(cfg: EngineConfig, meta: PlanMeta):
    """Build the single-round transition for this config + plan shape.

    Returns ``step(p, s, r_end)`` where ``p`` is the traced plan-array dict
    (see :func:`plan_device`), ``s`` the round state, and ``r_end`` the
    exclusive chunk bound that event leaps are clamped to. ``r_end`` is a
    traced scalar, so under the sweep driver's ``jax.vmap`` it becomes a
    *per-cell* bound: a lane whose bound is behind its round counter is
    select-masked (state bit-preserved) while groupmates keep running —
    the mechanism behind both heterogeneous event leaps within a group
    and the per-cell early exit in :mod:`repro.core.sweep`.

    Packed layout: the round unpacks the [SLOT_F, T] slot matrix into
    column locals, runs the protocol logic as straight-line column
    algebra, and repacks with a single ``jnp.stack`` at the end.
    Semantics are bit-identical to the frozen reference in
    ``repro.core.engine_legacy`` (golden traces + differential property
    tests enforce this).

    Grant-pass formulation: every non-ORTHRUS protocol has at most one
    pending lock request per slot (the ``kptr`` column), so FIFO grant
    decisions reduce to an all-pairs [T, T] enqueue-stamp comparison
    over compact [T] request vectors, and per-key same-round contention
    counts come from the carried ``agg_sum`` columns (one
    cancel-previous-and-apply scatter-add per column and round). This
    replaces the legacy engine's (key, enq) sort + segmented scans — the
    hottest ops of its round loop on saturated lock tables. ORTHRUS
    admits whole key-groups at once (several pending entries per slot),
    so it keeps the sorted segmented-grant path.
    """
    cm = cfg.cost
    T, K = cfg.n_slots, meta.max_keys
    R = meta.num_records
    N = meta.n_txns
    W = cfg.window
    n_cc = max(cfg.n_cc, 1)
    cap_keys = cm.cc_keys_per_round  # per CC lane per round, in key-ops
    has_lane_stream = meta.lane_cols > 0
    # open epoch arrival (fig15): admission additionally waits for the
    # txn's epoch to arrive. Off by default; the off path compiles to
    # the pre-model graph (golden traces stay bit-identical).
    open_arrival = cfg.epoch_interval_rounds > 0
    # overload robustness layer: policy / backoff / burst kinds are
    # compile-time statics; their parameters ride the plan dict as
    # traced scalars (pol_*). All off by default — the off paths are
    # the pre-layer graph.
    policy = cfg.admission_policy
    exp_backoff = cfg.backoff_mode == "exp"
    has_budget = cfg.retry_budget > 0
    bursty = cfg.arrival_pattern != "uniform"

    lane_of = jnp.arange(T, dtype=jnp.int32) // W
    slot_ids = jnp.arange(T, dtype=jnp.int32)
    kk = jnp.arange(K, dtype=jnp.int32)
    i32 = jnp.int32
    # metrics: powers of two for the log-bucket index (integer compare
    # count — exact, so dense/leap and vmap/serial agree bit-for-bit),
    # and the queue-depth sample grid positions
    lat_pow2 = jnp.asarray([1 << k for k in range(LAT_BUCKETS - 1)], i32)
    qgrid_pos = jnp.arange(QDEPTH_SAMPLES, dtype=i32) + 1

    lock_op_cycles = (
        cm.partition_lock_cycles
        if cfg.protocol == "partitioned_store"
        else cm.lock_op_cycles
    )
    # Shared-index cache penalty (paper §4.3): partitioned-store and SPLIT
    # variants probe thread-local indexes; everyone else shares one index.
    shared_index = cfg.protocol != "partitioned_store" and not cfg.split_index
    exec_cycles_per_op = cm.exec_op_cycles + (
        cm.shared_index_penalty_cycles if shared_index else 0
    )
    dl = cfg.deadlock_scheme
    dl_wait_cycles = {
        "waitfor": cm.waitfor_maintain_cycles,
        "dreadlocks": cm.dreadlocks_spin_cycles,
    }.get(dl, 0)
    # compact CSR release / wait-for path (EngineConfig.release_path)
    use_csr = cfg.release_path == "csr" and not cfg.is_orthrus
    need_rdr = use_csr and dl != "none"
    rdr_word = slot_ids // 32  # reader-bitmask word / bit per slot
    rdr_bit = jnp.int32(1) << (slot_ids % 32)
    # the slots of word w: a static slice, so each word's scatter takes
    # only its own slots' entries
    rdr_slots = [slice(32 * w, 32 * (w + 1)) for w in range((T + 31) // 32)]
    use_pallas = cfg.is_orthrus and _use_pallas(cfg)
    if use_pallas:
        from repro.kernels.lock_grant.ops import grant_sorted as _grant_sorted
        from repro.kernels.segscan import TILE

        # one grid step up to 8 (8, 128) tiles (the op rounds the block
        # up to whole tiles)
        grant_block = min(8 * TILE, T * K)

    def rounds_of(cyc):
        return (cyc + cm.cycles_per_round - 1) // cm.cycles_per_round

    def step(p, s, r_end):
        r = s["r"]
        wkeys = p["keys"]
        wmodes = p["modes"]
        wpart = p["part"]
        sc_all = p["txn_scalars"]  # [N, 4] = (nkeys, exec_ops, ollp, miss)
        lane_stream = p["lane_stream"] if has_lane_stream else None

        sl = s["slots"]
        tid = sl[C_TID]
        widx = sl[C_WIDX]
        lane_ctr = sl[C_LANE_CTR]
        ts = sl[C_TS]
        phase = sl[C_PHASE]
        committing = sl[C_COMMITTING] != 0
        busy_until = sl[C_BUSY_UNTIL]
        busy_kind = sl[C_BUSY_KIND]
        kptr = sl[C_KPTR]
        attempt = sl[C_ATTEMPT]
        ccptr = sl[C_CCPTR]
        msg_arrive = sl[C_MSG_ARRIVE]
        msg_stage = sl[C_MSG_STAGE]
        release_at = sl[C_RELEASE_AT]
        waited = sl[C_WAITED] != 0
        dl_debt = sl[C_DL_DEBT]
        arrive = sl[C_ARRIVE]

        free = busy_until <= r

        if open_arrival:
            # closed forms over the arrival schedule (saturating: ids /
            # rounds past the int32-exact range read as "never")
            def arr_of(g):
                # arrival round of global txn id g (the workload wraps
                # modulo N every arrive_cycle rounds)
                return p["arrive_round"][g % N] + _sat_mul(
                    g // N, p["arrive_cycle"]
                )

            def arrived_by(x):
                # txns with arrival round <= x — the exact inverse of
                # arr_of: arrived_by(x) > g  iff  x >= arr_of(g)
                cyc = p["arrive_cycle"]
                xp = jnp.maximum(x, 0)
                if bursty:
                    in_cyc = jnp.searchsorted(
                        p["ep_arrive"], xp % cyc, side="right"
                    ).astype(i32) * p["epoch_txns"]
                else:
                    in_cyc = (
                        xp % cyc // p["epoch_interval"] + 1
                    ) * p["epoch_txns"]
                n_in = jnp.minimum(in_cyc, N)
                return jnp.where(
                    x < 0, 0, _sat_mul(xp // cyc, N) + n_in
                )

        # --------------------------------------- 1a. admission-control drops
        with jax.named_scope("stage.admit"):
            # Queue-side policy drops advance next_txn *before* slot ranking,
            # so dropped txns are never loaded and cost nothing downstream.
            # Drops happen only at executed rounds; the stage-12 leap
            # candidates guarantee none falls strictly inside a leap gap, so
            # the counters are bit-identical dense vs leaped.
            if policy == "bounded_backlog":
                # drop the oldest waiters beyond the backlog cap
                drop = jnp.maximum(
                    arrived_by(r) - p["pol_cap"] - s["next_txn"], 0
                )
                s["pol_rejected"] = s["pol_rejected"] + drop
                s["next_txn"] = s["next_txn"] + drop
            elif policy == "deadline_shed":
                # drop waiters whose queueing delay exceeds the deadline:
                # txns arrived by r - deadline - 1 have waited > deadline
                drop = jnp.maximum(
                    arrived_by(r - p["pol_deadline"] - 1) - s["next_txn"], 0
                )
                s["pol_shed"] = s["pol_shed"] + drop
                s["next_txn"] = s["next_txn"] + drop

            # ------------------------------------------ 1+2. admission & retry
            # New admissions (EMPTY slots) and backoff->retry (BACKOFF slots
            # whose timer expired) are disjoint and share most column resets,
            # so they run as one fused masked update.
            empty = phase == EMPTY
            if lane_stream is None:
                rank = jnp.cumsum(empty.astype(i32)) - 1
                new_tid = s["next_txn"] + rank
                if open_arrival:
                    # global txn id g arrives with its epoch; arrival is
                    # monotone in g, so the admitted set is a prefix of the
                    # ranked empty slots and tids stay contiguous
                    arr_t = arr_of(new_tid)
                    adm = empty & (arr_t <= r)
                    if policy == "token_bucket":
                        # backpressure, no drops: txn g additionally waits
                        # for token g — the bucket starts with token_burst
                        # and refills one every token_interval_rounds
                        # (cost_model.token_grant is the host oracle). The
                        # gate loosens as g falls, so the admitted set is
                        # still a prefix of the ranked empty slots.
                        adm = adm & (
                            new_tid
                            < p["pol_tb_burst"] + r // p["pol_tb_iv"]
                        )
                        s["pol_tb_adm"] = s["pol_tb_adm"] + adm.sum(
                            dtype=i32
                        )
                else:
                    adm = empty
                new_widx = new_tid % N
                s["next_txn"] = s["next_txn"] + adm.sum(dtype=i32)
            else:
                # H-Store routing: each worker lane pulls the next txn homed to
                # its partition (lanes with no homed txns stay idle).
                M = meta.lane_cols
                new_widx = lane_stream[slot_ids, lane_ctr % M]
                adm = empty & (new_widx >= 0)
                new_tid = lane_ctr * T + slot_ids
                lane_ctr = jnp.where(adm, lane_ctr + 1, lane_ctr)
                s["next_txn"] = s["next_txn"] + adm.sum(dtype=i32)
            retry = (phase == BACKOFF) & free
            reset = adm | retry
            widx = jnp.where(adm, new_widx, widx)
            tid = jnp.where(adm, new_tid, tid)
            ts = jnp.where(adm, new_tid, ts)
            # metrics: stamp the txn's arrival round — its epoch arrival
            # under open arrival (latency then includes queueing delay), the
            # admission round under closed loop. Retries keep the stamp, so
            # latency spans aborts end-to-end.
            arrive = jnp.where(adm, arr_t if open_arrival else r, arrive)
            attempt = jnp.where(adm, 0, jnp.where(retry, attempt + 1, attempt))
            # per-slot workload columns for the loaded txns (the scalar
            # per-txn fields ride one fused [N, 4] gather)
            wsafe = jnp.where(tid >= 0, widx % N, 0)
            keys = wkeys[wsafe]
            modes = wmodes[wsafe]
            ccids = wpart[wsafe] % n_cc
            sc = sc_all[wsafe]
            nkeys = sc[:, 0]
            execops = sc[:, 1]
            ollp = sc[:, 2] != 0
            miss = sc[:, 3] != 0
            kvalid = kk[None, :] < nkeys[:, None]
            init_busy = rounds_of(
                cm.txn_fixed_cycles
                + jnp.where(ollp, cm.recon_cycles, 0)
            )
            phase = jnp.where(reset, INIT, phase)
            busy_until = jnp.where(
                adm,
                r + init_busy,
                jnp.where(retry, r + rounds_of(cm.txn_fixed_cycles), busy_until),
            )
            busy_kind = jnp.where(reset, CAT_LOCK, busy_kind)
            for f in ("want", "granted", "adm_done", "rel_done"):
                s[f] = jnp.where(reset[:, None], False, s[f])
            kptr = jnp.where(reset, 0, kptr)
            ccptr = jnp.where(reset, 0, ccptr)
            waited = jnp.where(reset, False, waited)

            free = busy_until <= r

        # ------------------------------------------------ 3. INIT -> acquire
        with jax.named_scope("stage.acquire"):
            start = (phase == INIT) & free & (tid >= 0)
            if cfg.is_orthrus:
                phase = jnp.where(start, MSG, phase)
                msg_stage = jnp.where(start, 0, msg_stage)
                msg_arrive = jnp.where(start, r + cm.msg_hop_rounds, msg_arrive)
            else:
                phase = jnp.where(start, ACQ, phase)

        # ------------------------------------------------ 4. ORTHRUS CC work
        with jax.named_scope("stage.cc"):
            if cfg.is_orthrus:
                # -- admission of acquire-messages and release-messages, bounded
                #    by each CC lane's per-round key-op capacity, in ts order.
                in_cur_group = (
                    (kk[None, :] >= ccptr[:, None])
                    & kvalid
                    & (ccids == jnp.take_along_axis(
                        ccids, jnp.minimum(ccptr, K - 1)[:, None], axis=1))
                )
                acq_cand = (
                    (phase == MSG) & (msg_stage == 0) & (msg_arrive <= r)
                )
                acq_keys = acq_cand[:, None] & in_cur_group & ~s["adm_done"]
                rel_cand = (phase == REL) & (release_at <= r)
                rel_keys = rel_cand[:, None] & s["granted"] & ~s["rel_done"]
                # Rank every active entry within its CC lane by (ts, key slot)
                # — the admission order — without sorting all T*K entries: a
                # slot's entries share its (unique) ts, so a [T] slot sort plus
                # per-CC prefix counts reproduces the (cc, ts, entry) rank
                # exactly at a fraction of the cost.
                act2d = acq_keys | rel_keys  # [T, K]
                cc_act = jnp.where(act2d, ccids, n_cc)
                cnt_tc = (
                    jnp.zeros((T, n_cc + 1), jnp.int32)
                    .at[jnp.broadcast_to(slot_ids[:, None], (T, K)), cc_act]
                    .add(1)
                )
                slot_order = jnp.argsort(ts, stable=True)  # ts unique
                cnt_sorted = cnt_tc[slot_order]
                excl_sorted = jnp.cumsum(cnt_sorted, axis=0) - cnt_sorted
                excl = jnp.zeros_like(excl_sorted).at[slot_order].set(excl_sorted)
                base_rank = jnp.take_along_axis(excl, cc_act, axis=1)
                same_cc_earlier = (
                    (cc_act[:, :, None] == cc_act[:, None, :])
                    & act2d[:, None, :]
                    & (kk[None, None, :] < kk[None, :, None])
                )
                within = same_cc_earlier.sum(-1, dtype=jnp.int32)
                seg_pos2d = base_rank + within + 1  # 1-based within CC lane
                proc2d = (seg_pos2d <= cap_keys) & act2d
                s["adm_done"] = s["adm_done"] | (proc2d & acq_keys.reshape(T, K))
                # group fully admitted -> requests live in the CC's lock table
                grp_all = jnp.where(in_cur_group, s["adm_done"], True).all(axis=1)
                admit_now = acq_cand & grp_all
                new_want = admit_now[:, None] & in_cur_group
                phase = jnp.where(admit_now, ACQ, phase)
                # release processing
                do_rel = proc2d & rel_keys.reshape(T, K)
                rel_k = jnp.where(do_rel, keys, 0)
                is_wr = do_rel & (modes == MODE_WRITE)
                s["wh"] = s["wh"].at[jnp.where(is_wr, rel_k, R)].set(
                    -1, mode="drop"
                )
                is_rd = do_rel & (modes == MODE_READ)
                s["rc"] = s["rc"].at[jnp.where(is_rd, rel_k, R)].add(
                    -1, mode="drop"
                )
                s["rel_done"] = s["rel_done"] | do_rel
                s["granted"] = s["granted"] & ~do_rel
            else:
                new_want = jnp.zeros((T, K), jnp.bool_)

        # ------------------------------------------------ 5. shared releases
        with jax.named_scope("stage.release"):
            rel_entries = jnp.zeros((T, K), jnp.bool_)
            if not cfg.is_orthrus:
                rel_now = (phase == REL) & (release_at <= r)
                rel_entries = rel_now[:, None] & s["granted"]
                rel_k = jnp.where(rel_entries, keys, 0)
                is_wr = rel_entries & (modes == MODE_WRITE)
                s["wh"] = s["wh"].at[jnp.where(is_wr, rel_k, R)].set(
                    -1, mode="drop"
                )
                is_rd = rel_entries & (modes == MODE_READ)
                s["rc"] = s["rc"].at[jnp.where(is_rd, rel_k, R)].add(
                    -1, mode="drop"
                )
                if need_rdr:
                    # clear the slot's reader bit once per *distinct*
                    # released read key: a slot releases all its granted
                    # entries at once, and re-entrant reads may hold several
                    # columns on one record — only the first contributes
                    dup = (
                        (keys[:, :, None] == keys[:, None, :])
                        & is_rd[:, None, :]
                        & (kk[None, None, :] < kk[None, :, None])
                    ).any(-1)
                    first_rd = is_rd & ~dup
                    rel_idx = jnp.where(first_rd, rel_k, R)
                    rel_bit = jnp.where(first_rd, -rdr_bit[:, None], 0)
                    s["rdr"] = tuple(
                        col.at[rel_idx[ws]].add(rel_bit[ws], mode="drop")
                        for col, ws in zip(s["rdr"], rdr_slots)
                    )
                s["granted"] = s["granted"] & ~rel_entries

        # ------------------------------------------------ 6. requests: want
        with jax.named_scope("stage.request"):
            if cfg.is_orthrus:
                s["want"] = s["want"] | new_want
                want_new = new_want
            else:
                # 2PL/DF/pstore: single in-flight request at kptr when ACQ & free
                at_k = kk[None, :] == kptr[:, None]
                need = (
                    ((phase == ACQ) & free)[:, None]
                    & at_k
                    & kvalid
                    & ~s["granted"]
                    & ~s["want"]
                )
                want_new = need
                s["want"] = s["want"] | need

            # assign enqueue order stamps to new queue entries
            if cfg.is_orthrus:
                flat_new = want_new.reshape(-1)
                new_rank = jnp.cumsum(flat_new.astype(jnp.int32)) - 1
                enq_val = (s["enq_ctr"] + new_rank).reshape(T, K)
                s["enq"] = jnp.where(want_new, enq_val, s["enq"])
                n_new = flat_new.sum(dtype=jnp.int32)
            else:
                # <= 1 new request per slot: rank over [T], same stamps as the
                # row-major flat cumsum (one entry per row)
                new_t = want_new.any(axis=1)
                new_rank = jnp.cumsum(new_t.astype(jnp.int32)) - 1
                s["enq"] = jnp.where(
                    want_new, (s["enq_ctr"] + new_rank)[:, None], s["enq"]
                )
                n_new = new_t.sum(dtype=jnp.int32)
            # releases consume stamp ids too (bit-compatible with the sorted
            # grant pass, where they participate as REQ_RELEASE entries)
            s["enq_ctr"] = s["enq_ctr"] + n_new + rel_entries.sum(dtype=jnp.int32)

        # ------------------------------------------------ 7. grant pass
        with jax.named_scope("stage.grant"):
            # Requests are live only while their slot is acquiring.
            pend2d = s["want"] & ~s["granted"] & (phase == ACQ)[:, None]
            newop2d = want_new | rel_entries  # fresh lock-table ops this round
            if cfg.is_orthrus:
                ent_kind = jnp.where(
                    pend2d,
                    jnp.where(modes == MODE_WRITE, REQ_WRITE, REQ_READ),
                    jnp.where(rel_entries, REQ_RELEASE, REQ_NONE),
                ).reshape(-1)
                ent_key = jnp.where(
                    (pend2d | rel_entries), keys, KEY_SENTINEL
                ).reshape(-1)
                ent_enq = s["enq"].reshape(-1)
                with jax.named_scope("kernel.lock_grant"):
                    # one sort by (key, enq) carrying kind and entry
                    # index; the lock table read at the sorted keys
                    e = sort_requests(
                        ent_key, ent_enq, ent_kind, s["wh"], s["rc"], R
                    )
                    if use_pallas:
                        # the Pallas segmented-grant kernel (compiled on
                        # TPU/GPU, interpreted elsewhere — see
                        # kernels.resolve_interpret); bit-identical to the jnp
                        # path below, which is its oracle
                        g_sorted, _op_pos = _grant_sorted(
                            e.keys, e.kind, e.wh_free, e.rc,
                            block_n=grant_block,
                        )
                    else:
                        g_sorted, _cont, _new = segmented_grant(
                            e.keys, e.ts, e.kind, e.wh_free, e.rc
                        )
                # re-entrant grants bypass the FIFO: a slot re-requesting a key
                # it already write-holds is granted immediately (real
                # transactions touch the same row more than once; without this
                # they would deadlock on their own lock). Entry i is slot
                # i // K's column i % K.
                self_grant = (
                    ((e.kind == REQ_READ) | (e.kind == REQ_WRITE))
                    & e.in_range
                    & (e.holder == e.index // K)
                )
                grant = unsort(g_sorted | self_grant, e.index).reshape(T, K)

                # apply grants to the lock table
                gk = jnp.where(grant, keys, 0)
                g_wr = grant & (modes == MODE_WRITE)
                g_rd = grant & (modes == MODE_READ)
                holder = jnp.broadcast_to(slot_ids[:, None], (T, K))
                s["wh"] = s["wh"].at[jnp.where(g_wr, gk, R)].set(
                    holder, mode="drop"
                )
                s["rc"] = s["rc"].at[jnp.where(g_rd, gk, R)].add(1, mode="drop")
            else:
                # single pending request per slot, at column kptr: FIFO
                # decisions among the <= T compact requests via an all-pairs
                # [T, T] key comparison — no sort, no scatter
                kptr_c = jnp.minimum(kptr, K - 1)[:, None]
                pend_t = jnp.take_along_axis(pend2d, kptr_c, axis=1).squeeze(1)
                rkey = jnp.take_along_axis(keys, kptr_c, axis=1).squeeze(1)
                renq = jnp.take_along_axis(s["enq"], kptr_c, axis=1).squeeze(1)
                rmode = jnp.take_along_axis(modes, kptr_c, axis=1).squeeze(1)
                is_wr_req = pend_t & (rmode == MODE_WRITE)
                if use_csr:
                    # compact CSR grant: sort the <= T pending requests by
                    # (key, stamp), take segmented minima of the enqueue
                    # stamps, and scatter them back to slot order — work
                    # sized to the request list (O(T log T)) instead of the
                    # all-pairs [T, T] stamp comparison below. Non-pending
                    # slots sort to a sentinel segment whose minima are
                    # never read (grant_t is masked by pend_t).
                    skey = jnp.where(pend_t, rkey, _IMAX)
                    order = lex_order(skey, renq)
                    ks = skey[order]
                    eqs = renq[order]
                    seg_start = jnp.concatenate(
                        [jnp.ones((1,), jnp.bool_), ks[1:] != ks[:-1]]
                    )
                    seg_id = jnp.cumsum(seg_start.astype(i32)) - 1
                    min_req_seg = jax.ops.segment_min(
                        eqs, seg_id, num_segments=T
                    )
                    min_wr_seg = jax.ops.segment_min(
                        jnp.where(is_wr_req[order], eqs, _IMAX), seg_id,
                        num_segments=T,
                    )
                    min_req = (
                        jnp.zeros((T,), i32).at[order].set(min_req_seg[seg_id])
                    )
                    min_wr = (
                        jnp.zeros((T,), i32).at[order].set(min_wr_seg[seg_id])
                    )
                else:
                    same_key = (rkey[None, :] == rkey[:, None]) & pend_t[None, :]
                    enq_b = jnp.broadcast_to(renq[None, :], (T, T))
                    min_wr = jnp.min(
                        jnp.where(same_key & is_wr_req[None, :], enq_b, _IMAX),
                        axis=1,
                    )
                    min_req = jnp.min(jnp.where(same_key, enq_b, _IMAX), axis=1)
                rkey_c = jnp.minimum(rkey, R - 1)
                whv = s["wh"][rkey_c]
                rc_t = s["rc"][rkey_c]
                wh_free_t = whv == -1
                # read grant: write-free record, no older write request queued;
                # write grant: write-free, zero read holders, oldest request.
                # enq stamps are unique, so strict compares are exact.
                grant_rd = wh_free_t & (min_wr > renq)
                grant_wr = wh_free_t & (rc_t == 0) & (min_req == renq)
                grant_t = pend_t & jnp.where(
                    rmode == MODE_WRITE, grant_wr, grant_rd
                )
                # re-entrant grants bypass the FIFO (see the ORTHRUS path)
                grant_t = grant_t | (pend_t & (whv == slot_ids))
                grant = pend2d & grant_t[:, None]

                # apply grants to the lock table ([T]-sized scatters: only the
                # kptr column can be granted)
                g_wr_t = grant_t & (rmode == MODE_WRITE)
                g_rd_t = grant_t & (rmode == MODE_READ)
                s["wh"] = s["wh"].at[jnp.where(g_wr_t, rkey, R)].set(
                    slot_ids, mode="drop"
                )
                s["rc"] = s["rc"].at[jnp.where(g_rd_t, rkey, R)].add(
                    1, mode="drop"
                )
                if need_rdr:
                    # reader bitmask: set the slot's bit on its *first*
                    # granted read column of the record — a re-entrant read
                    # on a key the slot already read-holds increments rc
                    # (per-entry, symmetric with release) but the bit tracks
                    # distinct membership
                    already = (
                        (keys == rkey[:, None])
                        & s["granted"]
                        & (modes == MODE_READ)
                    ).any(axis=1)
                    new_rd = g_rd_t & ~already
                    new_idx = jnp.where(new_rd, rkey, R)
                    new_bit = jnp.where(new_rd, rdr_bit, 0)
                    s["rdr"] = tuple(
                        col.at[new_idx[ws]].add(new_bit[ws], mode="drop")
                        for col, ws in zip(s["rdr"], rdr_slots)
                    )
            s["granted"] = s["granted"] | grant

        # ------------------------------------------------ 8. deadlock logic
        with jax.named_scope("stage.deadlock"):
            # (runs before cost charging so a wait-die "die" probe — a read of
            # the holder's timestamp — costs latency but does not occupy the
            # record's meta-data line the way a queue mutation does)
            abort_dl = jnp.zeros((T,), jnp.bool_)
            if dl != "none":
                kptr_c = jnp.minimum(kptr, K - 1)[:, None]
                waitkey = jnp.where(
                    (phase == ACQ)
                    & jnp.take_along_axis(
                        s["want"] & ~s["granted"], kptr_c, axis=1
                    ).squeeze(1),
                    jnp.take_along_axis(keys, kptr_c, axis=1).squeeze(1),
                    KEY_SENTINEL,
                )
                waiting = waitkey != KEY_SENTINEL
                mymode = jnp.take_along_axis(modes, kptr_c, axis=1).squeeze(1)
                # adj[t,u]: t waits on a lock u holds in a conflicting mode
                if use_csr:
                    # compact wait-for: the writer holding my key is one
                    # lock-table gather; read holders come from the carried
                    # per-record reader bitmask (bit u % 32 of word u // 32)
                    # — [T] + W [T] gathers and a [T, T] bit extraction
                    # replace the dense [T, T, K] key-equality tensor. A
                    # read holder conflicts only with a write waiter; a
                    # write holder conflicts with everyone.
                    wt_c = jnp.minimum(waitkey, R - 1)
                    hw = s["wh"][wt_c]  # [T] writer of my key (-1 = none)
                    dig = jnp.stack(
                        [col[wt_c] for col in s["rdr"]], axis=1
                    )  # [T, W] packed reader bits
                    rd_bits = jnp.take(dig, rdr_word, axis=1)  # [T, T]
                    adj_rd = ((rd_bits >> (slot_ids % 32)[None, :]) & 1) != 0
                    adj = (
                        (
                            (slot_ids[None, :] == hw[:, None])
                            | (adj_rd & (mymode == MODE_WRITE)[:, None])
                        )
                        & waiting[:, None]
                        & (slot_ids[None, :] != slot_ids[:, None])
                        & (tid[None, :] >= 0)
                    )
                else:
                    key_eq = keys[None, :, :] == waitkey[:, None, None]  # [t,u,k]
                    conflict = (mymode[:, None, None] == MODE_WRITE) | (
                        modes[None, :, :] == MODE_WRITE
                    )
                    adj = (
                        (key_eq & s["granted"][None, :, :] & conflict).any(-1)
                        & waiting[:, None]
                        & (slot_ids[None, :] != slot_ids[:, None])
                        & (tid[None, :] >= 0)
                    )
                if dl == "waitdie":
                    # a waiter dies whenever its wait-for edge points at an
                    # older holder — evaluated on every holder change (waiting
                    # on a younger holder is legal, so the edge must be
                    # re-checked when the lock changes hands); the "die" probe
                    # is a read of the holder's timestamp and is costed as
                    # latency only (no line occupancy) in stage 9
                    newly_waiting = waiting & ~waited
                    older_holder = (
                        adj & (ts[None, :] < ts[:, None])
                    ).any(-1)
                    abort_dl = older_holder & waiting
                    dl_debt = dl_debt + jnp.where(
                        newly_waiting, cm.waitdie_check_cycles, 0
                    )
                else:
                    own = jnp.eye(T, dtype=jnp.bool_)
                    # one propagation step per round (dreadlocks-style digests)
                    reach = own | (adj @ s["reach"])
                    s["reach"] = jnp.where(waiting[:, None], reach, own)
                    in_cycle = (adj & s["reach"].T).any(-1)  # holder reaches me
                    # abort the youngest member of the detected cycle; waitfor
                    # and dreadlocks are logically equivalent detectors (paper
                    # §4.1) and differ only in their cost constants
                    scc = s["reach"] & s["reach"].T
                    scc_ts_max = jnp.max(
                        jnp.where(scc & in_cycle[None, :], ts[None, :], -1),
                        axis=1,
                    )
                    abort_dl = in_cycle & (ts >= scc_ts_max)
                    dl_debt = dl_debt + jnp.where(waiting, dl_wait_cycles, 0)
                waited = waiting
                # convert deadlock-handling debt into lane busy time
                debt_rounds = dl_debt // cm.cycles_per_round
                has_debt = debt_rounds > 0
                busy_until = jnp.where(
                    has_debt, jnp.maximum(busy_until, r) + debt_rounds,
                    busy_until,
                )
                busy_kind = jnp.where(has_debt, CAT_DL, busy_kind)
                dl_debt = dl_debt % cm.cycles_per_round

                abort_dl = abort_dl & waiting
                s["aborts_dl"] = s["aborts_dl"] + abort_dl.sum(dtype=jnp.int32)
                s["wasted"] = s["wasted"] + jnp.where(abort_dl, kptr, 0).sum(
                    dtype=jnp.int32
                )
                phase = jnp.where(abort_dl, REL, phase)
                committing = jnp.where(abort_dl, False, committing)
                release_at = jnp.where(abort_dl, r, release_at)
                s["want"] = s["want"] & ~abort_dl[:, None]

        # ------------------------------------------------ 9. line-cost model
        with jax.named_scope("stage.line_cost"):
            # Coherence physics for shared lock tables (paper §2.1): each record's
            # CC meta-data line is a serially-reusable resource. Op service time
            # grows with the number of cores recently touching the line ("sharer
            # heat", estimated over epoch windows) and with line ping-pong (last
            # toucher on a different core). Queue-mutating ops on a backlogged
            # line wait behind it; wait-die "die" probes pay their own transfer
            # latency but occupy nothing. ORTHRUS CC lanes are exempt:
            # single-owner meta-data.
            if not cfg.is_orthrus:
                newop = newop2d  # fresh lock-table ops this round: reqs+releases
                mutate = newop & ~abort_dl[:, None]  # dies don't enqueue
                # per-key same-round contention via the carried agg_sum
                # columns (active entries, new ops, queue mutations). One
                # scatter-add per column and round cancels the previous
                # round's contributions and applies this round's, so each
                # column holds exactly "this round" when gathered and is
                # never read before a pending mutation (no [R]-sized copy,
                # see _state0).
                active2d = pend2d | rel_entries
                aidx = jnp.where(active2d, keys, R)
                sum_upd = (
                    active2d.astype(i32), newop.astype(i32), mutate.astype(i32)
                )
                idx_cat = jnp.concatenate([s["agg_prev_idx"], aidx], axis=0)
                s["agg_sum"] = tuple(
                    col.at[idx_cat].add(
                        jnp.concatenate([-prev, upd], axis=0), mode="drop"
                    )
                    for col, prev, upd in zip(
                        s["agg_sum"], s["agg_prev_upd"], sum_upd
                    )
                )
                s["agg_prev_idx"] = aidx
                s["agg_prev_upd"] = sum_upd
                e = r >> EPOCH_BITS
                opk_r = jnp.minimum(jnp.where(newop, keys, 0), R - 1)
                # this round's per-key totals
                contend, new_in_seg, mut_in_seg = (
                    col[opk_r] for col in s["agg_sum"]
                )
                ep_k, cur_k, prev_k = (col[opk_r] for col in s["heat"])
                lnf_cur, last_lane = (col[opk_r] for col in s["line"])
                sharers = jnp.where(
                    ep_k == e,
                    jnp.maximum(prev_k, cur_k),
                    jnp.where(ep_k == e - 1, cur_k, 0),
                )
                lane2d = jnp.broadcast_to(lane_of[:, None], (T, K))
                remote = last_lane != lane2d
                coh = jnp.where(
                    remote,
                    cm.coherence_cycles_per_sharer
                    * jnp.clip(sharers, 1, cfg.n_exec - 1),
                    0,
                )
                if dl == "dreadlocks":
                    # waiters spin on the holders' digests: every queued waiter
                    # keeps the lock meta-data lines hot, so each op pays extra
                    # coherence proportional to the current queue (paper §4.4.1)
                    coh = coh + cm.dreadlocks_spin_cycles * jnp.maximum(
                        contend - 1, 0
                    )
                dur = rounds_of(lock_op_cycles + coh)
                backlog = jnp.maximum(jnp.where(mutate, lnf_cur - r, 0), 0)
                charge = jnp.where(newop, backlog + dur, 0).sum(axis=1)
                # occupancy: same-round queue mutations serialize on the line
                occupy = jnp.where(mutate, mut_in_seg * dur, 0)
                tgt = jnp.maximum(lnf_cur, r) + occupy
                opk_heat = jnp.where(newop, opk_r, R)
                # lnf applies only at mutating entries (a die probe occupies
                # nothing), masked inside the max via INT32_MIN; last_lane
                # applies at every fresh op. Heat values are
                # per-key-identical, so duplicate-index set is idempotent.
                line_upd = (
                    jnp.where(mutate, tgt, jnp.iinfo(jnp.int32).min), lane2d
                )
                s["line"] = tuple(
                    col.at[opk_heat].max(upd, mode="drop")
                    for col, upd in zip(s["line"], line_upd)
                )
                new_prev = jnp.where(
                    ep_k == e, prev_k, jnp.where(ep_k == e - 1, cur_k, 0)
                )
                new_cur = jnp.where(ep_k == e, cur_k, 0) + new_in_seg
                heat_upd = (
                    jnp.broadcast_to(e, new_cur.shape), new_cur, new_prev
                )
                s["heat"] = tuple(
                    col.at[opk_heat].set(upd, mode="drop")
                    for col, upd in zip(s["heat"], heat_upd)
                )
                charged = charge > 0
                busy_until = jnp.where(
                    charged, jnp.maximum(busy_until, r) + charge,
                    busy_until,
                )
                busy_kind = jnp.where(charged, CAT_LOCK, busy_kind)

        # ------------------------------------------------ 10. transitions
        with jax.named_scope("stage.transition"):
            free = busy_until <= r
            exec_rounds_one = rounds_of(exec_cycles_per_op)

            if cfg.is_dynamic_2pl:
                cur_granted = jnp.take_along_axis(
                    s["granted"], jnp.minimum(kptr, K - 1)[:, None], axis=1
                ).squeeze(1)
                go = (phase == ACQ) & free & cur_granted & ~abort_dl
                last = go & (kptr + 1 >= nkeys)
                extra = jnp.maximum(execops - nkeys, 0)
                add = jnp.where(
                    go, exec_rounds_one + jnp.where(last, extra * exec_rounds_one, 0), 0
                )
                busy_until = jnp.where(
                    go, jnp.maximum(busy_until, r) + add, busy_until
                )
                busy_kind = jnp.where(go, CAT_EXEC, busy_kind)
                kptr = jnp.where(go, kptr + 1, kptr)
                phase = jnp.where(last, EXEC, phase)
            elif cfg.protocol in ("deadlock_free", "partitioned_store"):
                cur_granted = jnp.take_along_axis(
                    s["granted"], jnp.minimum(kptr, K - 1)[:, None], axis=1
                ).squeeze(1)
                go = (phase == ACQ) & free & cur_granted
                kptr = jnp.where(go, kptr + 1, kptr)
                alldone = go & (kptr >= nkeys)
                phase = jnp.where(alldone, EXEC, phase)
                busy_until = jnp.where(
                    alldone,
                    jnp.maximum(busy_until, r) + execops * exec_rounds_one,
                    busy_until,
                )
                busy_kind = jnp.where(alldone, CAT_EXEC, busy_kind)
            else:  # orthrus
                in_cur_group = (
                    (kk[None, :] >= ccptr[:, None])
                    & kvalid
                    & (ccids == jnp.take_along_axis(
                        ccids, jnp.minimum(ccptr, K - 1)[:, None], axis=1))
                )
                grp_done = (
                    (phase == ACQ)
                    & jnp.where(in_cur_group, s["granted"], True).all(axis=1)
                )
                nxt_cc = jnp.where(
                    (kk[None, :] >= ccptr[:, None]) & kvalid & ~in_cur_group,
                    kk[None, :],
                    K,
                ).min(axis=1)
                more = grp_done & (nxt_cc < K)
                ccptr = jnp.where(more, nxt_cc, ccptr)
                s["adm_done"] = jnp.where(more[:, None], False, s["adm_done"])
                phase = jnp.where(grp_done, MSG, phase)
                msg_stage = jnp.where(grp_done, jnp.where(more, 0, 1), msg_stage)
                msg_arrive = jnp.where(
                    grp_done, r + cm.msg_hop_rounds, msg_arrive
                )
                # response arrives -> READY
                resp = (
                    (phase == MSG) & (msg_stage == 1) & (msg_arrive <= r)
                )
                phase = jnp.where(resp, READY, phase)
                # exec-lane scheduling: oldest READY per idle lane starts
                lane_busy = jax.ops.segment_sum(
                    ((phase == EXEC) & ~free).astype(jnp.int32),
                    lane_of,
                    num_segments=cfg.n_exec,
                )
                ready = phase == READY
                ready_ts = jnp.where(ready, ts, jnp.iinfo(jnp.int32).max)
                lane_min = jax.ops.segment_min(
                    ready_ts, lane_of, num_segments=cfg.n_exec
                )
                startx = (
                    ready
                    & (ready_ts == lane_min[lane_of])
                    & (lane_busy[lane_of] == 0)
                )
                # break ties (same ts impossible — tids unique) -> safe
                phase = jnp.where(startx, EXEC, phase)
                busy_until = jnp.where(
                    startx, r + execops * exec_rounds_one, busy_until
                )
                busy_kind = jnp.where(startx, CAT_EXEC, busy_kind)

            # EXEC finished -> release (commit, or OLLP-miss abort+retry)
            free = busy_until <= r
            fin = (phase == EXEC) & free
            is_miss = fin & miss & (attempt == 0)
            s["aborts_ollp"] = s["aborts_ollp"] + is_miss.sum(dtype=jnp.int32)
            s["wasted"] = s["wasted"] + jnp.where(is_miss, execops, 0).sum(
                dtype=jnp.int32
            )
            phase = jnp.where(fin, REL, phase)
            committing = jnp.where(fin, ~is_miss, committing)
            rel_delay = cm.msg_hop_rounds if cfg.is_orthrus else 0
            release_at = jnp.where(fin, r + rel_delay, release_at)
            s["rel_done"] = jnp.where(fin[:, None], False, s["rel_done"])
            s["want"] = s["want"] & ~fin[:, None]

            # REL complete -> EMPTY (commit) or BACKOFF (retry). A slot leaves
            # only after every lock it held has actually been released (the
            # release scatter runs in stages 4/5 of a *subsequent* round).
            rel_done_all = (
                (phase == REL)
                & (release_at <= r)
                & ~(s["granted"]).any(axis=1)
            )
            com = rel_done_all & committing
            s["commits"] = s["commits"] + com.sum(dtype=jnp.int32)
            # metrics: commit-latency histogram (log-bucketed; bucket = count
            # of powers of two <= latency). Commits only happen at executed
            # rounds, so the scatter is bit-identical under event leaping.
            lat = r - arrive
            lat_b = jnp.sum(
                lat[:, None] >= lat_pow2[None, :], axis=1, dtype=jnp.int32
            )
            s["lat_hist"] = s["lat_hist"].at[
                jnp.where(com, lat_b, LAT_BUCKETS)
            ].add(1, mode="drop")
            aborting = rel_done_all & ~committing
            if exp_backoff:
                # bounded exponential backoff: base << attempt, shift-capped
                # then clamped (deterministic integer math on C_ATTEMPT —
                # cost_model.exp_backoff_rounds is the host oracle)
                bo = jnp.minimum(
                    cm.abort_backoff_rounds
                    << jnp.minimum(attempt, BACKOFF_SHIFT_CAP),
                    p["pol_bo_max"],
                )
            else:
                bo = cm.abort_backoff_rounds
            if has_budget or policy == "deadline_shed":
                # give-up paths: a retrying txn is dropped instead of backing
                # off when its retry budget is spent (pol_sacrificed, checked
                # first) or, under deadline_shed, when its end-to-end latency
                # has already blown the deadline (pol_timedout)
                give_up = jnp.zeros((T,), jnp.bool_)
                if has_budget:
                    sac = aborting & (attempt + 1 >= p["pol_retry_budget"])
                    s["pol_sacrificed"] = (
                        s["pol_sacrificed"] + sac.sum(dtype=i32)
                    )
                    give_up = give_up | sac
                if policy == "deadline_shed":
                    timed = (
                        aborting & ~give_up
                        & (r - arrive > p["pol_deadline"])
                    )
                    s["pol_timedout"] = (
                        s["pol_timedout"] + timed.sum(dtype=i32)
                    )
                    give_up = give_up | timed
                leave = committing | give_up
                drop_tid = com | give_up
                back = aborting & ~give_up
            else:
                leave = committing
                drop_tid = com
                back = aborting
            if exp_backoff:
                s["pol_backoff_rounds"] = s["pol_backoff_rounds"] + jnp.where(
                    back, bo, 0
                ).sum(dtype=i32)
            phase = jnp.where(
                rel_done_all, jnp.where(leave, EMPTY, BACKOFF), phase
            )
            tid = jnp.where(drop_tid, -1, tid)
            busy_until = jnp.where(back, r + bo, busy_until)
            s["want"] = jnp.where(rel_done_all[:, None], False, s["want"])

        # ------------------------------------------------ 11. lane accounting
        with jax.named_scope("stage.accounting"):
            busy = busy_until > r
            slot_cat = jnp.where(
                busy,
                busy_kind,
                jnp.where(
                    (phase == ACQ) & (s["want"] & ~s["granted"]).any(axis=1),
                    CAT_WAIT,
                    jnp.where(
                        (phase == MSG) | (phase == READY) | (phase == REL),
                        CAT_MSG,
                        CAT_IDLE,
                    ),
                ),
            )
            if cfg.is_orthrus:
                # a lane is "exec" if its running slot is busy executing; else
                # classify by the most advanced outstanding slot state
                lane_exec = jax.ops.segment_max(
                    (busy & (slot_cat == CAT_EXEC)).astype(jnp.int32), lane_of,
                    num_segments=cfg.n_exec,
                )
                lane_wait = jax.ops.segment_max(
                    (slot_cat == CAT_WAIT).astype(jnp.int32), lane_of,
                    num_segments=cfg.n_exec,
                )
                lane_msg = jax.ops.segment_max(
                    (slot_cat == CAT_MSG).astype(jnp.int32), lane_of,
                    num_segments=cfg.n_exec,
                )
                lane_cat = jnp.where(
                    lane_exec == 1,
                    CAT_EXEC,
                    jnp.where(lane_wait == 1, CAT_WAIT,
                              jnp.where(lane_msg == 1, CAT_MSG, CAT_IDLE)),
                )
                cat_counts = jax.ops.segment_sum(
                    jnp.ones((cfg.n_exec,), jnp.int32),
                    lane_cat,
                    num_segments=NCAT,
                )
            else:
                cat_counts = jax.ops.segment_sum(
                    jnp.ones((T,), jnp.int32), slot_cat, num_segments=NCAT
                )

        # ------------------------------------------------ 12. event leap
        with jax.named_scope("stage.leap"):
            # Advance straight to the next round at which any slot can act.
            # Every skipped round is provably a no-op: every per-slot timer
            # (busy_until / msg_arrive / release_at) lies beyond it and no slot
            # is in a phase that acts unconditionally each round. Lane
            # accounting is exact because the post-transition lane state (the
            # `cat_counts` just computed) persists unchanged through the gap.
            if cfg.event_leap:
                busy2 = busy_until > r
                free2 = ~busy2
                # future per-slot timers; a busy expiry is always an event (it
                # changes lane accounting even when no transition follows)
                cand = jnp.where(busy2, busy_until, _IMAX)
                # admission, release processing and message arrival ignore the
                # busy timer (stages 1, 4, 5 have no `free` gate), so their
                # timers and ready-to-act states are tracked unconditionally
                cand = jnp.minimum(cand, jnp.where(
                    (phase == MSG) & (msg_arrive > r), msg_arrive, _IMAX))
                cand = jnp.minimum(cand, jnp.where(
                    (phase == REL) & (release_at > r), release_at, _IMAX))
                if lane_stream is None:
                    if open_arrival:
                        # the earliest admissible txn is global id next_txn
                        # (post-admission); an empty slot acts once it has
                        # arrived, and its arrival round is the wake-up
                        # event until then (arrival is monotone in g, so no
                        # admission can happen sooner)
                        g0 = s["next_txn"]
                        arr0 = arr_of(g0)
                        if policy == "token_bucket":
                            # admission additionally waits for token g0:
                            # earliest grant round is the host oracle
                            # cost_model.token_ready_round
                            arr0 = jnp.maximum(arr0, _sat_mul(
                                jnp.maximum(g0 - p["pol_tb_burst"] + 1, 0),
                                p["pol_tb_iv"],
                            ))
                        can_adm = jnp.broadcast_to(arr0 <= r + 1, (T,))
                        cand = jnp.minimum(cand, jnp.where(
                            (phase == EMPTY).any(), arr0, _IMAX))
                        # policy drop events are wake-ups in their own right
                        # (not gated on an EMPTY slot): the next drop round
                        # is closed-form in next_txn, so leaping lands on
                        # it exactly and stage 1a stays dense-identical
                        if policy == "bounded_backlog":
                            cand = jnp.minimum(
                                cand, arr_of(g0 + p["pol_cap"])
                            )
                        elif policy == "deadline_shed":
                            cand = jnp.minimum(
                                cand, arr0 + p["pol_deadline"] + 1
                            )
                    else:
                        can_adm = jnp.ones((T,), jnp.bool_)
                else:
                    can_adm = (
                        lane_stream[slot_ids, lane_ctr % meta.lane_cols] >= 0
                    )
                act_next = (
                    ((phase == EMPTY) & can_adm)
                    | ((phase == MSG) & (msg_arrive <= r))
                    | ((phase == REL) & (release_at <= r))
                    | (free2 & ((phase == INIT) | (phase == BACKOFF)))
                )
                if cfg.is_orthrus:
                    # a READY slot starts the round its lane goes idle; while
                    # the lane runs another slot, that slot's busy_until is the
                    # wake-up event (already a candidate above)
                    lane_exec_busy = jax.ops.segment_max(
                        ((phase == EXEC) & busy2).astype(jnp.int32), lane_of,
                        num_segments=cfg.n_exec,
                    )
                    act_next = act_next | (
                        (phase == READY) & (lane_exec_busy[lane_of] == 0)
                    )
                else:
                    # an acquiring slot with no pending (un-granted) request
                    # places its next one immediately; a blocked waiter is
                    # woken by its holder's release timer
                    blocked = jnp.take_along_axis(
                        s["want"] & ~s["granted"],
                        jnp.minimum(kptr, K - 1)[:, None], axis=1
                    ).squeeze(1)
                    act_next = act_next | ((phase == ACQ) & free2 & ~blocked)
                if dl in ("waitfor", "dreadlocks"):
                    # graph detectors evolve every waiting round (reach-matrix
                    # propagation + per-round spin debt): stay dense while any
                    # slot waits
                    act_next = act_next | waited.any()
                cand = jnp.where(act_next, r + 1, cand)
                nxt = jnp.clip(jnp.min(cand), r + 1, r_end)
            else:
                nxt = r + 1
            leap = nxt - r
            s["cat"] = s["cat"] + cat_counts * leap
            s["steps"] = s["steps"] + 1
            s["r"] = nxt
            # metrics: queue samples at every grid point in (r, nxt]. The
            # post-transition slot state persists unchanged through a leap
            # gap and arrivals are closed-form in the round, so each grid
            # point observes exactly what the dense loop would record.
            qgrid = qgrid_pos * p["qgrid_iv"]
            qm = (qgrid > r) & (qgrid <= nxt)
            s["q_inflight"] = jnp.where(
                qm, (tid >= 0).sum(dtype=i32), s["q_inflight"]
            )
            if open_arrival:
                # backlog at grid point x: txns arrived by x (closed form —
                # full workload cycles + whole epochs within the cycle,
                # capped at N per cycle) minus the admission cursor; policy
                # drops advance next_txn, so drops leave the backlog
                arrived = arrived_by(qgrid)
                s["q_depth"] = jnp.where(
                    qm, jnp.maximum(arrived - s["next_txn"], 0), s["q_depth"]
                )
        s["slots"] = jnp.stack(
            [tid, widx, lane_ctr, ts, phase, committing.astype(i32),
             busy_until, busy_kind, kptr, attempt, ccptr, msg_arrive,
             msg_stage, release_at, waited.astype(i32), dl_debt, arrive],
            axis=0,
        )
        return s

    return step


def _batch_plan_rounds(cfg: EngineConfig, plan: planner_lib.Plan):
    """Per-batch planning latency in rounds: planner lanes place every
    key-op into the dependency graph / queues and run OLLP reconnaissance
    for data-dependent access sets (P1: planners, not exec lanes).

    The scheduled family charges the (cheaper) clusterer instead —
    hash each access, union each scanned conflict edge, append each
    txn to its cluster queue (``CostModel.scheduler_batch_cycles``) —
    divided by the same pipelined lane count."""
    cm = cfg.cost
    sched = plan.sched
    n_ollp = np.bincount(
        sched.batch_of, weights=plan.ollp.astype(np.int64),
        minlength=sched.num_batches,
    )
    if cfg.protocol == "scheduled":
        work = cm.scheduler_batch_cycles(
            n_txns=sched.batch_size.astype(np.int64),
            n_ops=sched.plan_ops.astype(np.int64),
            n_edges=sched.scan_edges.astype(np.int64),
            n_ollp=n_ollp.astype(np.int64),
        )
    else:
        work = (
            sched.plan_ops.astype(np.int64) * cm.batch_plan_cycles_per_op
            + n_ollp.astype(np.int64) * cm.recon_cycles
        )
    plan_cycles = work // max(cfg.n_cc, 1)
    return np.asarray(cm.rounds(plan_cycles), np.int32)  # [NB]


def _planner_work_rounds(cfg: EngineConfig, plan: planner_lib.Plan):
    """Per-batch planner-lane work (rounds) under the throughput model
    (``cfg.n_planner_lanes > 0``): one lane plans the whole batch, and
    the work scales with the batch's conflict-graph size — transactions,
    key-ops, dependency edges (fragment-granular in fragment mode),
    fragments, and OLLP reconnaissance. Unlike :func:`_batch_plan_rounds`
    this is *not* divided by a lane count: planner parallelism is across
    batches (round-robin over the lanes), never within one.
    """
    cm = cfg.cost
    sched = plan.sched
    n_ollp = np.bincount(
        sched.batch_of, weights=plan.ollp.astype(np.int64),
        minlength=sched.num_batches,
    ).astype(np.int64)
    if cfg.protocol == "scheduled":
        # clusterer-lane work: scan the batch's full conflict graph
        # (``scan_edges``), not the per-cluster chains it collapses to
        cycles = cm.scheduler_batch_cycles(
            n_txns=sched.batch_size.astype(np.int64),
            n_ops=sched.plan_ops.astype(np.int64),
            n_edges=sched.scan_edges.astype(np.int64),
            n_ollp=n_ollp,
        )
        return np.asarray(cm.rounds(cycles), np.int32)  # [NB]
    if cfg.fragment_exec:
        n_edges = sched.frag_edges_per_batch()
        n_frags = sched.batch_fsize.astype(np.int64)
    else:
        n_edges = sched.edges_per_batch()
        n_frags = np.zeros(sched.num_batches, np.int64)
    cycles = cm.planner_batch_cycles(
        n_txns=sched.batch_size.astype(np.int64),
        n_ops=sched.plan_ops.astype(np.int64),
        n_edges=n_edges,
        n_frags=n_frags,
        n_ollp=n_ollp,
    )
    return np.asarray(cm.rounds(cycles), np.int32)  # [NB]


def _batch_state0(cfg: EngineConfig, plan: planner_lib.Plan, T: int):
    i32 = jnp.int32
    sched = plan.sched
    N = sched.n_txns
    s = dict(
        r=jnp.zeros((), i32),
        next_txn=jnp.zeros((), i32),
        cur_batch=jnp.zeros((), i32),
        bpos=jnp.zeros((), i32),
        batch_left=jnp.asarray(int(sched.batch_size[0]), i32),
        plan_fin=jnp.asarray(int(_batch_plan_rounds(cfg, plan)[0]), i32),
        done=jnp.zeros((N,), jnp.bool_),
        # all per-slot scalar fields: one [BATCH_SLOT_F, T] matrix (BC_*)
        slots=jnp.zeros((BATCH_SLOT_F, T), i32).at[BC_TID].set(-1),
        commits=jnp.zeros((), i32),
        aborts_dl=jnp.zeros((), i32),
        aborts_ollp=jnp.zeros((), i32),
        wasted=jnp.zeros((), i32),
        cat=jnp.zeros((NCAT,), i32),
        steps=jnp.zeros((), i32),
        # metrics: log-bucketed commit-latency histogram + queue-depth
        # samples on the fixed round grid (see repro.core.metrics)
        lat_hist=jnp.zeros((LAT_BUCKETS,), i32),
        q_depth=jnp.zeros((QDEPTH_SAMPLES,), i32),
        q_inflight=jnp.zeros((QDEPTH_SAMPLES,), i32),
    )
    if cfg.fragment_exec:
        # done flags live at fragment granularity; the commit barrier
        # counts down each txn's outstanding fragments
        s["done"] = jnp.zeros((sched.n_frags,), jnp.bool_)
        s["txn_left"] = jnp.asarray(sched.txn_nfrags, i32)
    if cfg.inter_batch_pipeline and sched.num_batches > 1:
        # cursor into the *next* batch's level-0 fragment prefix, plus
        # per-batch accounting of the overlap (Fig-10 split: how much
        # admission/commit traffic ran ahead of the batch barrier)
        s["pbpos"] = jnp.asarray(int(sched.batch_fstart[1]), i32)
        s["pipe_com"] = jnp.zeros((), i32)  # next-batch commits pending
        s["pipe_adm"] = jnp.zeros((), i32)  # cumulative early admissions
        s["pipe_commits"] = jnp.zeros((), i32)  # cumulative early commits
    if cfg.n_planner_lanes > 0 or cfg.epoch_interval_rounds > 0:
        s["epoch_ctr"] = jnp.zeros((), i32)  # global batch (epoch) index
    if cfg.admission_policy != "none":
        # overload-robustness counters (see _state0; the batch engine
        # sheds whole epochs, so timeouts never fire — no abort path)
        s["pol_rejected"] = jnp.zeros((), i32)
        s["pol_shed"] = jnp.zeros((), i32)
        s["pol_timedout"] = jnp.zeros((), i32)
        s["pol_tb_adm"] = jnp.zeros((), i32)
    if cfg.n_planner_lanes > 0:
        # planner-lane throughput model: batch 0 arrives at round 0 on a
        # free lane 0, so its plan completes after its own work span
        work = _planner_work_rounds(cfg, plan)
        ready0 = int(work[0])
        s["plan_fin"] = jnp.asarray(ready0, i32)
        s["lane_free"] = (
            jnp.zeros((cfg.n_planner_lanes,), i32).at[0].set(ready0)
        )
        s["plan_busy"] = jnp.asarray(ready0, i32)  # lane-busy rounds
        s["plan_qdelay"] = jnp.zeros((), i32)  # plan-queue wait rounds
        # round-granular lane-busy integral (fig15 utilization): each
        # lane's live planning span is [lane_start, lane_free); batch
        # 0's span [0, ready0) on lane 0 accrues per elapsed round
        s["lane_start"] = jnp.zeros((cfg.n_planner_lanes,), i32)
        s["pb_span"] = jnp.zeros((2,), i32)  # replaced-span remainder
        s["plan_busy_int"] = jnp.zeros((), i32)
    return s


def make_batch_step(cfg: EngineConfig, meta: PlanMeta):
    """Single-round transition for the batch-planned protocols (dgcc /
    quecc): lock-free execution over a precomputed dependency schedule.

    Returns ``step(p, s, r_end)`` with the same contract as
    :func:`make_step` (including the vmapped per-cell ``r_end``
    early-exit semantics). The round loop performs only (a) batch-boundary
    bookkeeping, (b) admission of the current batch's schedulable units
    to exec-lane slots, and (c) the wavefront-eligibility check "all
    planned predecessors committed" — the dense-gather formulation of
    the ``dep_wavefront`` kernel contract (equivalence is
    property-tested). There is no lock table, no deadlock logic, and no
    abort path. Per-slot scalars use the packed [BATCH_SLOT_F, T]
    matrix layout.

    The schedulable unit is a whole transaction by default, or a
    per-(txn, lane) *fragment* under ``cfg.fragment_exec``: slots then
    track fragments (BC_WIDX = fragment id, BC_FTXN = owning txn), the
    readiness check runs over the fragment-granular graph, and a txn
    commits when its last fragment finishes (the ``txn_left`` barrier
    counts down) — so a multi-partition transaction's per-lane work is
    no longer serialized behind one hot lane. With
    ``cfg.inter_batch_pipeline`` on top, level-0 fragments of batch b+1
    are admitted while batch b drains (DGCC §5), once b+1's plan is
    ready; ``pipe_adm`` / ``pipe_commits`` count the traffic that ran
    ahead of the barrier (the per-batch accounting split).
    """
    cm = cfg.cost
    T = cfg.n_slots
    N = meta.n_txns
    W = cfg.window
    NB = meta.num_batches
    frag = cfg.fragment_exec
    F = meta.n_frags
    # one batch cannot pipeline into itself (nothing to overlap)
    pipe = cfg.inter_batch_pipeline and NB > 1
    # planner-lane throughput model / open epoch arrival (fig15): both
    # default off, and the off path compiles to the pre-model graph —
    # golden traces stay bit-identical by construction
    L = cfg.n_planner_lanes
    planner_model = L > 0
    open_arrival = cfg.epoch_interval_rounds > 0
    # overload robustness (see make_step): the batch engine has no abort
    # path, so the layer reduces to epoch-granular admission control —
    # bounded_backlog / deadline_shed skip stale whole epochs at batch
    # rollover, token_bucket delays an epoch's plan start until its
    # token accrues. Policies exclude inter_batch_pipeline (asserted).
    policy = cfg.admission_policy
    bursty = cfg.arrival_pattern != "uniform"

    lane_of = jnp.arange(T, dtype=jnp.int32) // W
    slot_ids = jnp.arange(T, dtype=jnp.int32)
    shared_index = not cfg.split_index
    exec_cycles_per_op = cm.exec_op_cycles + (
        cm.shared_index_penalty_cycles if shared_index else 0
    )
    # Pallas readiness scan (EngineConfig.kernel_impl): the wavefront
    # check runs the dep_wavefront kernel over the loaded slots' edge
    # rows instead of the dense per-slot gather (its oracle)
    P = meta.frag_pred_width if frag else meta.pred_width
    use_pallas = _use_pallas(cfg) and P > 0
    if use_pallas:
        from repro.kernels.dep_wavefront.ops import (
            dep_wavefront_ready as _dep_ready,
        )
        from repro.kernels.segscan import TILE

        # one grid step up to 8 (8, 128) tiles (the op rounds the block
        # up to whole tiles)
        wave_block = min(8 * TILE, T * P)

    def rounds_of(cyc):
        return (cyc + cm.cycles_per_round - 1) // cm.cycles_per_round
    exec_rounds_one = rounds_of(exec_cycles_per_op)
    imax = jnp.iinfo(jnp.int32).max
    i32 = jnp.int32
    # metrics closure constants (see make_step)
    lat_pow2 = jnp.asarray([1 << k for k in range(LAT_BUCKETS - 1)], i32)
    qgrid_pos = jnp.arange(QDEPTH_SAMPLES, dtype=i32) + 1

    def step(p, s, r_end):
        r = s["r"]
        if frag:
            ne_all = p["frag_ne"]  # [F, 2] = (npred, exec_ops)
            pred_pad = p["frag_pred_pad"]  # [F, PF]
            unit_batch = p["frag_batch"]  # [F] batch of each fragment
            ustart = p["batch_fstart"]  # [NB] admission-unit ranges
            usize = p["batch_fsize"]
            NU = F
        else:
            ne_all = p["txn_ne"]  # [N, 2] = (npred, exec_ops)
            pred_pad = p["pred_pad"]  # [N, P]
            unit_batch = p["batch_of"]
            ustart = p["batch_start"]
            usize = p["batch_size"]
            NU = N
        batch_of = p["batch_of"]  # [N] txn-level (commit barrier)
        bsize = p["batch_size"]
        plan_rounds = p["plan_rounds"]  # [NB]
        if planner_model or open_arrival:
            interval = p["epoch_interval"]
        if open_arrival:
            # closed forms over the epoch-arrival schedule (saturating;
            # see make_step). Epoch g arrives whole at ep_arrival(g);
            # epochs_arrived_by is its exact inverse.
            if bursty:
                def ep_arrival(g):
                    return _sat_mul(
                        g // p["sched_epochs"], p["sched_period"]
                    ) + p["ep_sched"][g % p["sched_epochs"]]

                def epochs_arrived_by(x):
                    xp = jnp.maximum(x, 0)
                    cnt = _sat_mul(
                        xp // p["sched_period"], p["sched_epochs"]
                    ) + jnp.searchsorted(
                        p["ep_sched"], xp % p["sched_period"],
                        side="right",
                    ).astype(i32)
                    return jnp.where(x < 0, 0, cnt)
            else:
                def ep_arrival(g):
                    return _sat_mul(g, interval)

                def epochs_arrived_by(x):
                    return jnp.where(
                        x < 0, 0, jnp.maximum(x, 0) // interval + 1
                    )

            def units_before(g):
                # schedulable units in global epochs [0, g) (fragments
                # under frag mode; the workload wraps modulo NB)
                return _sat_mul(g // NB, NU) + p["cum_usize"][g % NB]

        sl = s["slots"]
        tid = sl[BC_TID]
        widx = sl[BC_WIDX]
        ts = sl[BC_TS]
        phase = sl[BC_PHASE]
        busy_until = sl[BC_BUSY_UNTIL]
        busy_kind = sl[BC_BUSY_KIND]
        msg_arrive = sl[BC_MSG_ARRIVE]
        ftxn = sl[BC_FTXN]
        arrive = sl[BC_ARRIVE]

        # -------------------------------------------- 1. batch rollover
        with jax.named_scope("stage.rollover"):
            # When every transaction of the current batch has committed, open
            # the next one. Planning models, in order of fidelity:
            #   * default: pipelined latency — planners started on the next
            #     batch the moment they finished this one, so the new
            #     batch's plan-ready round advances by its own planning span;
            #   * open arrival (epoch_interval_rounds > 0): same, but a plan
            #     cannot start before its batch arrives (epoch g arrives at
            #     round g * interval);
            #   * planner-lane throughput model (n_planner_lanes = L > 0):
            #     batch g is planned end-to-end by lane g % L; the plan
            #     starts at max(arrival, lane free) and occupies the lane
            #     for its conflict-graph-scaled work span, so high epoch
            #     rates queue plans behind saturated lanes (the fig15
            #     plateau). The schedule depends only on the arrival and
            #     work sequences (cost_model.planner_lane_schedule is the
            #     host-side oracle).
            adv = s["batch_left"] == 0
            if policy in ("bounded_backlog", "deadline_shed"):
                # epoch-granular shedding: at rollover (always an executed
                # round, so dense and leaped runs evaluate the same r) skip
                # straight past the epochs the queue policy has dropped —
                # those beyond the backlog cap (oldest first), or those
                # whose queueing delay already exceeds the deadline. The
                # dropped units advance next_txn so the backlog samples see
                # them leave the queue.
                g_next = s["epoch_ctr"] + 1
                if policy == "bounded_backlog":
                    floor_g = epochs_arrived_by(r) - p["pol_cap_epochs"]
                else:
                    floor_g = epochs_arrived_by(r - p["pol_deadline"] - 1)
                skip = jnp.where(adv, jnp.clip(floor_g - g_next, 0, _SAT), 0)
                dropped = units_before(g_next + skip) - units_before(g_next)
                ckey = (
                    "pol_rejected" if policy == "bounded_backlog"
                    else "pol_shed"
                )
                s[ckey] = s[ckey] + dropped
                s["next_txn"] = s["next_txn"] + dropped
            else:
                skip = 0
            new_b = jnp.where(
                adv, (s["cur_batch"] + 1 + skip) % NB, s["cur_batch"]
            )
            # stale flags (the workload wraps around modulo NB) are cleared
            # one batch ahead of admission: the incoming batch here, or the
            # incoming *pipeline* batch when early admission is on (the new
            # current batch's flags were cleared at the previous rollover)
            clr_b = (new_b + 1) % NB if pipe else new_b
            s["done"] = jnp.where(adv & (unit_batch == clr_b), False, s["done"])
            if frag:
                s["txn_left"] = jnp.where(
                    adv & (batch_of == clr_b), p["txn_nfrags"], s["txn_left"]
                )
            if pipe:
                # admission continues where the pipelined cursor stopped;
                # commits that ran ahead of the barrier are already paid
                s["bpos"] = jnp.where(adv, s["pbpos"], s["bpos"])
                s["pbpos"] = jnp.where(adv, ustart[clr_b], s["pbpos"])
                s["batch_left"] = jnp.where(
                    adv, bsize[new_b] - s["pipe_com"], s["batch_left"]
                )
                s["pipe_com"] = jnp.where(adv, 0, s["pipe_com"])
            else:
                s["bpos"] = jnp.where(adv, ustart[new_b], s["bpos"])
                s["batch_left"] = jnp.where(adv, bsize[new_b], s["batch_left"])
            if planner_model or open_arrival:
                g_new = s["epoch_ctr"] + 1 + skip  # new batch's global index
                if open_arrival:
                    arrive_new = ep_arrival(g_new)
                    if policy == "token_bucket":
                        # backpressure: epoch g's plan additionally waits
                        # for its (epoch-granular) token; the arrival stamp
                        # below keeps the true arrival round, so latency
                        # includes the token wait
                        arrive_new = jnp.maximum(arrive_new, _sat_mul(
                            jnp.maximum(
                                g_new - p["pol_tb_burst_e"] + 1, 0
                            ),
                            p["pol_tb_iv"],
                        ))
                else:
                    arrive_new = g_new * interval
            if planner_model:
                lane = g_new % L
                lane_free_prev = s["lane_free"][lane]
                ready = jnp.maximum(arrive_new, lane_free_prev) + p[
                    "plan_work"][new_b]
                s["plan_qdelay"] = s["plan_qdelay"] + jnp.where(
                    adv, jnp.maximum(lane_free_prev - arrive_new, 0), 0
                )
                s["plan_busy"] = s["plan_busy"] + jnp.where(
                    adv, p["plan_work"][new_b], 0
                )
                # Round-granular lane-busy integral. The schedule is
                # evaluated lazily at rollover, so the new span
                # [start_new, ready) may already be partly (or wholly) in
                # the past: credit its elapsed part now — never its future
                # part, which the per-step overlap accumulation (stage 8)
                # picks up as rounds elapse, keeping the integral <= L * r
                # at every instant (the fig15 >1.0-utilization fix). The
                # replaced span's unelapsed remainder is parked in the
                # pb_span carry; a carry overwritten while it still has a
                # remainder undercounts, which needs a plan to outlive L
                # subsequent batch executions (not observed in practice).
                start_new = jnp.maximum(arrive_new, lane_free_prev)
                elapsed_part = jnp.maximum(
                    jnp.minimum(ready, r) - start_new, 0
                )
                s["plan_busy_int"] = s["plan_busy_int"] + jnp.where(
                    adv, elapsed_part, 0
                )
                old_start = s["lane_start"][lane]
                keep_old = adv & (lane_free_prev > r)
                s["pb_span"] = jnp.where(
                    keep_old,
                    jnp.stack([jnp.maximum(old_start, r), lane_free_prev]),
                    s["pb_span"],
                )
                s["lane_start"] = s["lane_start"].at[lane].set(
                    jnp.where(adv, start_new, old_start)
                )
                s["lane_free"] = s["lane_free"].at[lane].set(
                    jnp.where(adv, ready, lane_free_prev)
                )
                new_plan_fin = ready
            elif open_arrival:
                new_plan_fin = (
                    jnp.maximum(arrive_new, s["plan_fin"]) + plan_rounds[new_b]
                )
            else:
                new_plan_fin = s["plan_fin"] + plan_rounds[new_b]
            s["plan_fin"] = jnp.where(adv, new_plan_fin, s["plan_fin"])
            if planner_model or open_arrival:
                s["epoch_ctr"] = s["epoch_ctr"] + adv.astype(jnp.int32) + skip
            s["cur_batch"] = new_b

            def next_plan_fin(nb):
                # modeled plan-ready round of the *next* batch (global epoch
                # epoch_ctr + 1): what the pipelined level-0 prefix waits
                # for — the plan, not the batch barrier. Identical to the
                # value the rollover above will commit for that batch
                # (lane_free is only written at rollovers).
                if planner_model:
                    g_nxt = s["epoch_ctr"] + 1
                    a_nxt = (
                        ep_arrival(g_nxt) if open_arrival
                        else g_nxt * interval
                    )
                    return jnp.maximum(
                        a_nxt, s["lane_free"][g_nxt % L]
                    ) + p["plan_work"][nb]
                if open_arrival:
                    return jnp.maximum(
                        ep_arrival(s["epoch_ctr"] + 1), s["plan_fin"]
                    ) + plan_rounds[nb]
                return s["plan_fin"] + plan_rounds[nb]

        # -------------------------------------------- 2. admission
        with jax.named_scope("stage.admit"):
            # Empty slots pull the next positions of the current batch, in
            # the planner's serial order, once the batch's plan is ready.
            # Unit positions index transactions (txn mode) or fragments in
            # admission order (fragment mode).
            empty = phase == EMPTY
            rank = jnp.cumsum(empty.astype(jnp.int32)) - 1
            pos = s["bpos"] + rank
            bend = ustart[s["cur_batch"]] + usize[s["cur_batch"]]
            if pipe:
                # ranks beyond the current batch's remaining units spill into
                # the next batch's level-0 fragment prefix (its plan finishes
                # one planning span after the current one's)
                cur_avail = jnp.maximum(bend - s["bpos"], 0)
                adm_cur = empty & (rank < cur_avail) & (r >= s["plan_fin"])
                nb = (s["cur_batch"] + 1) % NB
                nlvl_end = ustart[nb] + p["lvl0_fcount"][nb]
                plan_fin_next = next_plan_fin(nb)
                ppos = s["pbpos"] + (rank - cur_avail)
                adm_pipe = (
                    empty
                    & (rank >= cur_avail)
                    & (ppos < nlvl_end)
                    & (r >= plan_fin_next)
                )
                adm = adm_cur | adm_pipe
                upos = jnp.where(adm_pipe, ppos, pos)
                s["bpos"] = s["bpos"] + adm_cur.sum(dtype=jnp.int32)
                n_pipe = adm_pipe.sum(dtype=jnp.int32)
                s["pbpos"] = s["pbpos"] + n_pipe
                s["pipe_adm"] = s["pipe_adm"] + n_pipe
                n_adm = adm.sum(dtype=jnp.int32)
            else:
                adm = empty & (pos < bend) & (r >= s["plan_fin"])
                upos = pos
                n_adm = adm.sum(dtype=jnp.int32)
                s["bpos"] = s["bpos"] + n_adm
            widx = jnp.where(adm, upos, widx)
            new_tid = s["next_txn"] + rank
            tid = jnp.where(adm, new_tid, tid)
            ts = jnp.where(adm, new_tid, ts)
            # metrics: stamp the unit's arrival round — its epoch's arrival
            # under open arrival (pipelined early admissions belong to the
            # *next* epoch), the admission round under closed loop
            if open_arrival:
                arr_cur = ep_arrival(s["epoch_ctr"])
                if pipe:
                    arr_new = jnp.where(
                        adm_pipe, ep_arrival(s["epoch_ctr"] + 1), arr_cur
                    )
                else:
                    arr_new = arr_cur
                arrive = jnp.where(adm, arr_new, arrive)
            else:
                arrive = jnp.where(adm, r, arrive)
            s["next_txn"] = s["next_txn"] + n_adm
            if policy == "token_bucket":
                s["pol_tb_adm"] = s["pol_tb_adm"] + n_adm
            if frag:
                ftxn = jnp.where(
                    adm, p["frag_txn"][jnp.clip(widx, 0, F - 1)], ftxn
                )
            else:
                ftxn = jnp.where(adm, widx, ftxn)
            # one fused [T, 2] gather: (npred, exec_ops); widx is fixed for
            # the rest of the round, so the predecessor rows gathered here
            # serve both the wavefront check and the event leap
            ne = ne_all[widx]
            npred_t = ne[:, 0]
            exec_t = ne[:, 1]
            preds = pred_pad[widx]  # [T, P]
            init_busy = rounds_of(
                cm.txn_fixed_cycles + npred_t * cm.dep_check_cycles
            )
            phase = jnp.where(adm, INIT, phase)
            busy_until = jnp.where(adm, r + init_busy, busy_until)
            busy_kind = jnp.where(adm, CAT_LOCK, busy_kind)

        # -------------------------------------------- 3. INIT -> MSG
        with jax.named_scope("stage.msg"):
            # The exec lane fetches its next planned entry from the scheduler
            # queue: one SPSC hop (functional separation, as in ORTHRUS).
            free = busy_until <= r
            start = (phase == INIT) & free & (tid >= 0)
            phase = jnp.where(start, MSG, phase)
            msg_arrive = jnp.where(start, r + cm.msg_hop_rounds, msg_arrive)
            got = (phase == MSG) & (msg_arrive <= r)
            phase = jnp.where(got, READY, phase)

        # -------------------------------------------- 4. wavefront check
        with jax.named_scope("stage.wavefront"):
            # "All planned predecessors committed" — the dep_wavefront
            # primitive, either in dense per-slot form or as the Pallas
            # segmented edge scan over the loaded slots' rows
            # (fragment-granular when cfg.fragment_exec: preds are
            # fragments, done is [F]).
            with jax.named_scope("kernel.dep_wavefront"):
                if use_pallas:
                    # one edge per (slot, pred) pair; stale slots contribute
                    # duplicate copies of real rows (or sentinel padding),
                    # which cannot change any unit's readiness
                    edge_dst = jnp.where(
                        preds >= 0,
                        jnp.broadcast_to(widx[:, None], preds.shape),
                        KEY_SENTINEL,
                    ).reshape(-1)
                    edge_src = jnp.maximum(preds, 0).reshape(-1)
                    ready_u = _dep_ready(
                        edge_dst, edge_src, s["done"], num_txns=NU,
                        block_n=wave_block,
                    )
                    dep_ok = ready_u[widx]
                else:
                    pred_ok = (preds < 0) | s["done"][jnp.maximum(preds, 0)]
                    dep_ok = pred_ok.all(axis=1)
            ready = (phase == READY) & dep_ok

        # -------------------------------------------- 5. lane scheduling
        with jax.named_scope("stage.schedule"):
            busy = busy_until > r
            lane_busy = jax.ops.segment_sum(
                ((phase == EXEC) & busy).astype(jnp.int32),
                lane_of,
                num_segments=cfg.n_exec,
            )
            ready_ts = jnp.where(ready, ts, imax)
            lane_min = jax.ops.segment_min(
                ready_ts, lane_of, num_segments=cfg.n_exec
            )
            startx = (
                ready
                & (ready_ts == lane_min[lane_of])
                & (lane_busy[lane_of] == 0)
            )
            phase = jnp.where(startx, EXEC, phase)
            busy_until = jnp.where(
                startx, r + exec_t * exec_rounds_one, busy_until
            )
            busy_kind = jnp.where(startx, CAT_EXEC, busy_kind)

        # -------------------------------------------- 6. commit
        with jax.named_scope("stage.commit"):
            # No locks to release and no abort path: planned execution is
            # conflict-free by construction. In fragment mode a finished
            # fragment marks itself done and decrements its transaction's
            # outstanding-fragment count; the txn commits (once) when the
            # count hits zero — the commit-when-all-fragments-done join.
            free = busy_until <= r
            fin = (phase == EXEC) & free
            s["done"] = s["done"].at[jnp.where(fin, widx, NU)].set(
                True, mode="drop"
            )
            if frag:
                tl = s["txn_left"].at[jnp.where(fin, ftxn, N)].add(
                    -1, mode="drop"
                )
                s["txn_left"] = tl
                tl_t = tl[jnp.where(fin, ftxn, 0)]
                com_slot = fin & (tl_t == 0)
                # several fragments of one txn can finish in the same round
                # on different slots: only the lowest such slot commits it
                same = (ftxn[None, :] == ftxn[:, None]) & com_slot[None, :]
                com_first = slot_ids == jnp.min(
                    jnp.where(same, slot_ids[None, :], T), axis=1
                )
                com = com_slot & com_first
                ncom = com.sum(dtype=jnp.int32)
                if pipe:
                    com_b = batch_of[jnp.where(com, ftxn, 0)]
                    ncom_ahead = (com & (com_b != s["cur_batch"])).sum(
                        dtype=jnp.int32
                    )
                    s["pipe_com"] = s["pipe_com"] + ncom_ahead
                    s["pipe_commits"] = s["pipe_commits"] + ncom_ahead
                    s["batch_left"] = s["batch_left"] - (ncom - ncom_ahead)
                else:
                    s["batch_left"] = s["batch_left"] - ncom
            else:
                ncom = fin.sum(dtype=jnp.int32)
                s["batch_left"] = s["batch_left"] - ncom
            s["commits"] = s["commits"] + ncom
            # metrics: commit-latency histogram (see make_step). In fragment
            # mode the committing slot is the one whose fragment completed
            # the txn, so its latency spans arrival -> last-fragment-done.
            com_mask = com if frag else fin
            lat = r - arrive
            lat_b = jnp.sum(
                lat[:, None] >= lat_pow2[None, :], axis=1, dtype=jnp.int32
            )
            s["lat_hist"] = s["lat_hist"].at[
                jnp.where(com_mask, lat_b, LAT_BUCKETS)
            ].add(1, mode="drop")
            phase = jnp.where(fin, EMPTY, phase)
            tid = jnp.where(fin, -1, tid)

        # -------------------------------------------- 7. lane accounting
        with jax.named_scope("stage.accounting"):
            busy2 = busy_until > r
            slot_cat = jnp.where(
                busy2,
                busy_kind,
                jnp.where(
                    phase == MSG,
                    CAT_MSG,
                    jnp.where(phase == READY, CAT_WAIT, CAT_IDLE),
                ),
            )
            lane_exec = jax.ops.segment_max(
                (busy2 & (slot_cat == CAT_EXEC)).astype(jnp.int32), lane_of,
                num_segments=cfg.n_exec,
            )
            lane_wait = jax.ops.segment_max(
                (slot_cat == CAT_WAIT).astype(jnp.int32), lane_of,
                num_segments=cfg.n_exec,
            )
            lane_msg = jax.ops.segment_max(
                (slot_cat == CAT_MSG).astype(jnp.int32), lane_of,
                num_segments=cfg.n_exec,
            )
            lane_cat = jnp.where(
                lane_exec == 1,
                CAT_EXEC,
                jnp.where(lane_wait == 1, CAT_WAIT,
                          jnp.where(lane_msg == 1, CAT_MSG, CAT_IDLE)),
            )
            cat_counts = jax.ops.segment_sum(
                jnp.ones((cfg.n_exec,), jnp.int32),
                lane_cat,
                num_segments=NCAT,
            )

        # -------------------------------------------- 8. event leap
        with jax.named_scope("stage.leap"):
            # Timers: busy_until (init dep-check spans, exec, pred commits),
            # msg_arrive, and the scalar admission gate (plan_fin / batch
            # rollover). A dep-blocked READY slot is woken by its predecessor's
            # commit (the pred's busy_until); a dep-clear READY slot starts the
            # round its lane goes idle.
            if cfg.event_leap:
                busy3 = busy_until > r
                free3 = ~busy3
                cand = jnp.where(busy3, busy_until, imax)
                cand = jnp.minimum(cand, jnp.where(
                    (phase == MSG) & (msg_arrive > r), msg_arrive, imax))
                act_next = (
                    (free3 & (phase == INIT))
                    | ((phase == MSG) & (msg_arrive <= r))
                )
                # same pred rows as stage 4 (widx unchanged); `done` moved, so
                # the commit flags are re-gathered
                pred_ok2 = (preds < 0) | s["done"][jnp.maximum(preds, 0)]
                dep_ok2 = pred_ok2.all(axis=1)
                lane_exec_busy = jax.ops.segment_max(
                    ((phase == EXEC) & busy3).astype(jnp.int32), lane_of,
                    num_segments=cfg.n_exec,
                )
                act_next = act_next | (
                    (phase == READY) & dep_ok2 & (lane_exec_busy[lane_of] == 0)
                )
                cand = jnp.where(act_next, r + 1, cand)
                # admission is a scalar event: the next batch opens the round
                # after batch_left hits zero; within a batch, empty slots admit
                # once plan_fin has passed and positions remain
                bend2 = ustart[s["cur_batch"]] + usize[s["cur_batch"]]
                adm_evt = jnp.where(
                    s["batch_left"] == 0,
                    r + 1,
                    jnp.where(
                        s["bpos"] < bend2,
                        jnp.maximum(s["plan_fin"], r + 1),
                        imax,
                    ),
                )
                if pipe:
                    # pipelined admission wakes when the next batch's plan
                    # lands, while level-0 fragment positions remain
                    nb2 = (s["cur_batch"] + 1) % NB
                    nlvl_end2 = ustart[nb2] + p["lvl0_fcount"][nb2]
                    pipe_evt = jnp.where(
                        s["pbpos"] < nlvl_end2,
                        jnp.maximum(next_plan_fin(nb2), r + 1),
                        imax,
                    )
                    adm_evt = jnp.minimum(adm_evt, pipe_evt)
                adm_evt = jnp.where((phase == EMPTY).any(), adm_evt, imax)
                nxt = jnp.clip(jnp.minimum(jnp.min(cand), adm_evt), r + 1, r_end)
            else:
                nxt = r + 1
            leap = nxt - r
            s["cat"] = s["cat"] + cat_counts * leap
            s["steps"] = s["steps"] + 1
            s["r"] = nxt
            if planner_model:
                # round-granular planner-busy: overlap of each lane's live
                # span (and the carry span) with the elapsed window [r, nxt)
                # — spans only move at rollovers, which are always executed
                # rounds, so the sum is bit-identical under event leaping
                acc = jnp.maximum(
                    jnp.minimum(s["lane_free"], nxt)
                    - jnp.maximum(s["lane_start"], r),
                    0,
                ).sum(dtype=i32)
                acc = acc + jnp.maximum(
                    jnp.minimum(s["pb_span"][1], nxt)
                    - jnp.maximum(s["pb_span"][0], r),
                    0,
                )
                s["plan_busy_int"] = s["plan_busy_int"] + acc
            # metrics: queue samples at every grid point in (r, nxt] (see
            # make_step — post-transition state persists through the gap,
            # and epoch arrivals are closed-form in the round)
            qgrid = qgrid_pos * p["qgrid_iv"]
            qm = (qgrid > r) & (qgrid <= nxt)
            s["q_inflight"] = jnp.where(
                qm, (tid >= 0).sum(dtype=i32), s["q_inflight"]
            )
            if open_arrival:
                # backlog in admission units (fragments under frag mode, to
                # match next_txn's granularity): all units of the epochs
                # arrived by grid point x, minus the admission cursor
                # (policy drops advance the cursor, leaving the backlog)
                n_arr = epochs_arrived_by(qgrid)
                arrived = units_before(n_arr)
                s["q_depth"] = jnp.where(
                    qm, jnp.maximum(arrived - s["next_txn"], 0), s["q_depth"]
                )
        s["slots"] = jnp.stack(
            [tid, widx, ts, phase, busy_until, busy_kind, msg_arrive, ftxn,
             arrive],
            axis=0,
        )
        return s

    return step


def _compact_keys(plan: planner_lib.Plan) -> planner_lib.Plan:
    """Remap record keys to a dense id space (simulation-side compaction).

    np.unique is monotone, so canonical (sorted) acquisition orders are
    preserved; only the lock-table array size changes (10M-record tables
    would otherwise dominate simulator memory traffic). The dense space is
    padded up to a power-of-two bucket: padding records are never touched
    by any key (all reads are masked by ``in_rng`` / ``kvalid``), so the
    simulation is unchanged, while cells whose true record counts differ
    only slightly land in the same bucket and share one compilation.
    """
    keys = plan.keys
    uniq, inv = np.unique(keys, return_inverse=True)
    dense = inv.reshape(keys.shape).astype(np.int32)
    num = len(uniq)
    if uniq[-1] == int(KEY_SENTINEL):  # keep padding as sentinel
        dense = np.where(keys == int(KEY_SENTINEL), int(KEY_SENTINEL), dense)
        num -= 1
    num = max(int(num), 1)
    # 25% headroom before rounding up, so sweep cells whose distinct-key
    # counts straddle a power of two still land in one bucket
    r_pad = max(16, 1 << (num + (num >> 2) - 1).bit_length())
    plan = dataclasses.replace(plan, keys=dense, num_records=r_pad)
    return plan


def make_plan(cfg: EngineConfig, workload: Workload) -> planner_lib.Plan:
    """Plan the workload for the protocol (engine-ready arrays)."""
    with jax.profiler.TraceAnnotation("repro.plan", protocol=cfg.protocol):
        if cfg.protocol == "orthrus":
            plan = planner_lib.plan_orthrus(workload, cfg.n_cc)
        elif cfg.protocol == "deadlock_free":
            plan = planner_lib.plan_sorted(workload)
        elif cfg.protocol == "partitioned_store":
            plan = planner_lib.plan_partition_store(workload, cfg.n_exec)
        elif cfg.protocol == "dgcc":
            plan = planner_lib.plan_dgcc(
                workload, workload.cfg.batch_epoch,
                n_lanes=max(cfg.n_cc, 1), fragments=cfg.fragment_exec,
            )
        elif cfg.protocol == "quecc":
            plan = planner_lib.plan_quecc(
                workload, max(cfg.n_cc, 1), workload.cfg.batch_epoch,
                fragments=cfg.fragment_exec,
            )
        elif cfg.protocol == "scheduled":
            # clusters round-robin over the *execution* lanes (there is no
            # planner-lane key partition to inherit)
            plan = planner_lib.plan_scheduled(
                workload, workload.cfg.batch_epoch, n_lanes=max(cfg.n_exec, 1),
            )
        else:
            plan = planner_lib.plan_dynamic(workload)
        plan.epoch_txns = workload.cfg.batch_epoch  # open-arrival epoch size
        if not cfg.is_batch_planned:
            plan = _compact_keys(plan)
        return plan


def run_simulation(
    cfg: EngineConfig,
    workload: Workload,
    seed: int = 0,
) -> SimResult:
    """Plan the workload for the protocol, then simulate.

    Routed through :mod:`repro.core.sweep`, which caches the compiled
    round-chunk runner across calls that share (protocol statics, plan
    shapes) — an entire figure sweep typically compiles once.
    """
    from repro.core import sweep as sweep_lib  # deferred: sweep imports us

    plan = make_plan(cfg, workload)
    return sweep_lib.simulate_plans(cfg, [plan])[0]
