"""Event-leaping engine: equivalence and sweep-driver identity.

The leaping engine's contract is *bit-identical simulation*: commits,
aborts (both kinds), wasted ops, round counts, and the Fig-10 lane-time
breakdown must match the dense reference loop exactly, for every
protocol — and the vmapped multi-cell driver must match serial
execution exactly. The same contract covers the packed [SLOT_F, T]
state-matrix engine vs the frozen pre-rewrite step builders
(``repro.core.engine_legacy``, selected with
``EngineConfig(state_layout="legacy")``). These tests are the guard
rail for any future engine change (see ENGINE_VERSION in
repro.core.sweep); tests/test_golden_traces.py pins the same contract
against committed fixtures across PRs.
"""

import dataclasses

import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.core import sweep
from repro.core.engine import EngineConfig, run_simulation
from repro.core.lockgrant import KEY_SENTINEL
from repro.core.workloads import (MODE_READ, MODE_WRITE, WorkloadConfig,
                                  make_workload)

FAST = dict(max_rounds=2000, warmup_rounds=500, chunk_rounds=500,
            target_commits=10**9)

PROTO_KW = {
    "twopl_waitdie": dict(n_exec=8),
    "twopl_waitfor": dict(n_exec=8),
    "twopl_dreadlocks": dict(n_exec=8),
    "deadlock_free": dict(n_exec=8),
    "orthrus": dict(n_cc=2, n_exec=6, window=2),
    "partitioned_store": dict(n_exec=8),
    "dgcc": dict(n_cc=2, n_exec=6, window=2),
    "quecc": dict(n_cc=4, n_exec=6, window=2),
}


def _fingerprint(res):
    """Everything the engine reports except wall-clock measurements."""
    return (
        res.commits,
        res.aborts_deadlock,
        res.aborts_ollp,
        res.wasted_ops,
        res.rounds,
        res.sim_seconds,
        tuple(sorted(res.breakdown.items())),
        res.raw["total_commits"],
        res.raw["next_txn"],
        res.raw["rounds_total"],
    )


def _run(protocol, wl, leap, sim=FAST):
    cfg = EngineConfig(protocol=protocol, event_leap=leap,
                       **PROTO_KW[protocol], **sim)
    return run_simulation(cfg, wl)


@pytest.fixture(scope="module")
def ycsb_hot():
    return make_workload(
        WorkloadConfig(kind="ycsb", num_txns=512, num_records=20_000,
                       num_hot=8, seed=0)
    )


@pytest.mark.parametrize("protocol", sorted(PROTO_KW))
def test_leap_matches_dense(ycsb_hot, protocol):
    leap = _run(protocol, ycsb_hot, leap=True)
    dense = _run(protocol, ycsb_hot, leap=False)
    assert _fingerprint(leap) == _fingerprint(dense)
    # leaping may only ever *reduce* the number of executed round steps
    assert leap.raw["steps_executed"] <= dense.raw["steps_executed"]
    assert dense.raw["steps_executed"] == dense.raw["rounds_total"]


def test_leap_actually_skips_rounds(ycsb_hot):
    """Batch-planned execution is mostly barrier waits: the leap must
    skip a large fraction of rounds (this is the perf mechanism — if it
    stops skipping, the speedup is silently gone)."""
    res = _run("dgcc", ycsb_hot, leap=True)
    assert res.raw["steps_executed"] < 0.7 * res.raw["rounds_total"]


def test_leap_matches_dense_tpcc_ollp():
    """TPC-C exercises OLLP reconnaissance, miss-aborts and retries."""
    wl = make_workload(
        WorkloadConfig(kind="tpcc", num_txns=512, num_warehouses=4,
                       ollp_miss_prob=0.5, seed=4)
    )
    for protocol in ("deadlock_free", "twopl_waitdie"):
        leap = _run(protocol, wl, leap=True)
        dense = _run(protocol, wl, leap=False)
        assert _fingerprint(leap) == _fingerprint(dense)
        if protocol == "deadlock_free":
            # dynamic 2PL reads indexes inline (its planner clears the
            # OLLP flags); the planned protocol must exercise the
            # miss-abort-retry path
            assert leap.aborts_ollp > 0


@settings(max_examples=6, deadline=None)
@given(
    protocol=st.sampled_from(sorted(PROTO_KW)),
    num_hot=st.sampled_from([0, 4, 64, 1024]),
    read_only=st.booleans(),
    seed=st.integers(min_value=0, max_value=3),
)
def test_leap_matches_dense_property(protocol, num_hot, read_only, seed):
    wl = make_workload(
        WorkloadConfig(kind="ycsb", num_txns=256, num_records=10_000,
                       num_hot=num_hot, read_only=read_only, seed=seed)
    )
    sim = dict(max_rounds=1000, warmup_rounds=250, chunk_rounds=250,
               target_commits=10**9)
    leap = _run(protocol, wl, leap=True, sim=sim)
    dense = _run(protocol, wl, leap=False, sim=sim)
    assert _fingerprint(leap) == _fingerprint(dense)


@pytest.fixture(scope="module")
def ycsb_hot_rw():
    """YCSB on 48 hot records in which each txn reads the keys of its own
    parity and writes the rest: hot records are read-shared by many slots
    at once, so write waiters' wait-for edges run through the reader
    bitmask (and no txn reads a record it then writes)."""
    wl = make_workload(
        WorkloadConfig(kind="ycsb", num_txns=512, num_records=20_000,
                       num_hot=48, seed=0)
    )
    parity = (wl.keys + np.arange(len(wl.keys))[:, None]) % 2
    modes = np.where(parity == 0, MODE_READ, MODE_WRITE)
    return dataclasses.replace(wl, modes=modes.astype(wl.modes.dtype))


# Past one 32-slot reader-bitmask word: 40 lanes take two words, 80
# take three.
LEGACY_CASES = [pytest.param(p, None, "ycsb_hot", id=p)
                for p in sorted(PROTO_KW)] + [
    pytest.param(p, n, "ycsb_hot_rw", id=f"{p}-{n}-rw")
    for p in ("twopl_dreadlocks", "twopl_waitdie", "twopl_waitfor")
    for n in (40, 80)
]


@pytest.mark.parametrize("protocol,n_exec,wl_fixture", LEGACY_CASES)
def test_packed_matches_legacy(request, protocol, n_exec, wl_fixture):
    """The packed [SLOT_F, T] state-matrix engine must reproduce the
    frozen pre-rewrite engine bit-exactly, per protocol."""
    wl = request.getfixturevalue(wl_fixture)
    kw = dict(PROTO_KW[protocol])
    if n_exec is not None:
        kw["n_exec"] = n_exec
    packed, legacy = (
        run_simulation(EngineConfig(protocol=protocol, event_leap=True,
                                    state_layout=layout, **kw, **FAST), wl)
        for layout in ("packed", "legacy")
    )
    assert _fingerprint(packed) == _fingerprint(legacy)
    assert packed.raw["steps_executed"] == legacy.raw["steps_executed"]
    assert packed.commits > 0


@settings(max_examples=8, deadline=None)
@given(
    protocol=st.sampled_from(sorted(PROTO_KW)),
    n_exec=st.sampled_from([2, 6, 16]),
    window=st.sampled_from([1, 3]),
    num_hot=st.sampled_from([0, 8, 512]),
    batch_epoch=st.sampled_from([64, 256]),
    event_leap=st.booleans(),
    seed=st.integers(min_value=0, max_value=3),
)
def test_packed_matches_legacy_property(protocol, n_exec, window, num_hot,
                                        batch_epoch, event_leap, seed):
    """Differential conformance: packed vs legacy over randomized
    (protocol, lane count, window, contention, batch epoch, leap mode)
    configurations — the full cross product the fig13 sweeps explore."""
    wl = make_workload(
        WorkloadConfig(kind="ycsb", num_txns=256, num_records=10_000,
                       num_hot=num_hot, batch_epoch=batch_epoch, seed=seed)
    )
    sim = dict(max_rounds=1000, warmup_rounds=250, chunk_rounds=250,
               target_commits=10**9)
    kw = dict(PROTO_KW[protocol])
    kw["n_exec"] = n_exec
    if protocol in ("orthrus", "dgcc", "quecc"):
        kw["window"] = window
    results = []
    for layout in ("packed", "legacy"):
        cfg = EngineConfig(protocol=protocol, event_leap=event_leap,
                           state_layout=layout, **kw, **sim)
        results.append(run_simulation(cfg, wl))
    assert _fingerprint(results[0]) == _fingerprint(results[1])


@pytest.fixture(scope="module")
def tpcc_repeats():
    """TPC-C on 50 stock records a warehouse: NewOrder's ten stock items
    repeat within a txn, so a slot re-requests a key it already
    write-holds and the grant pass grants it at once (re-entrant)."""
    return make_workload(
        WorkloadConfig(kind="tpcc", num_txns=512, num_warehouses=4,
                       stock_per_wh=50, seed=1)
    )


# T * K entries a grant pass (K = 12): 144 and 1200, neither a whole
# number of the kernel's 1024-entry tiles, so its blocks are padded
@pytest.mark.parametrize("n_exec,window", [(6, 2), (20, 5)])
def test_orthrus_grant_paths_match_legacy(tpcc_repeats, n_exec, window):
    """ORTHRUS's grant pass sorts its entries once, reads the lock table
    at the sorted keys and unsorts the grants once, for the jnp grant and
    the Pallas kernel (interpreted here) alike: both reproduce the frozen
    engine bit-exactly on a stream with re-entrant requests."""
    keys = tpcc_repeats.keys
    live = keys != KEY_SENTINEL
    repeats = [len(set(k[m])) < m.sum() for k, m in zip(keys, live)]
    assert sum(repeats) > 0
    kw = dict(protocol="orthrus", n_cc=2, n_exec=n_exec, window=window,
              event_leap=True, **FAST)
    assert (n_exec * window * keys.shape[1]) % 1024
    results = [
        run_simulation(EngineConfig(**kw, **extra), tpcc_repeats)
        for extra in (dict(kernel_impl="jnp"), dict(kernel_impl="pallas"),
                      dict(state_layout="legacy"))
    ]
    base = _fingerprint(results[0])
    assert results[0].commits > 0
    for res in results[1:]:
        assert _fingerprint(res) == base
        assert res.raw["steps_executed"] == results[0].raw["steps_executed"]


FRAG_SIM = dict(max_rounds=2500, warmup_rounds=500, chunk_rounds=500,
                target_commits=10**9)


@pytest.fixture(scope="module")
def ycsb_multipart():
    # every txn spans 2 partitions: the regime where per-lane fragments
    # differ from whole-txn scheduling
    return make_workload(
        WorkloadConfig(kind="ycsb", num_txns=256, num_records=10_000,
                       num_hot=8, multipart_frac=1.0, num_partitions=8,
                       batch_epoch=64, seed=0)
    )


@pytest.mark.parametrize("pipeline", [False, True])
@pytest.mark.parametrize("protocol", ["dgcc", "quecc"])
def test_fragment_leap_matches_dense(ycsb_multipart, protocol, pipeline):
    """Fragment-granular execution (and inter-batch pipelined admission)
    must leap bit-identically to its own dense round loop."""
    results = []
    for leap in (True, False):
        cfg = EngineConfig(protocol=protocol, event_leap=leap,
                           fragment_exec=True,
                           inter_batch_pipeline=pipeline,
                           **PROTO_KW[protocol], **FRAG_SIM)
        results.append(run_simulation(cfg, ycsb_multipart))
    assert _fingerprint(results[0]) == _fingerprint(results[1])
    assert (results[0].raw.get("pipe_adm")
            == results[1].raw.get("pipe_adm"))
    assert (results[0].raw["steps_executed"]
            <= results[1].raw["steps_executed"])


@settings(max_examples=8, deadline=None)
@given(
    protocol=st.sampled_from(["dgcc", "quecc"]),
    n_exec=st.sampled_from([2, 6, 16]),
    window=st.sampled_from([1, 3]),
    num_hot=st.sampled_from([0, 8, 512]),
    batch_epoch=st.sampled_from([64, 256]),
    seed=st.integers(min_value=0, max_value=3),
)
def test_fragment_off_matches_legacy_property(protocol, n_exec, window,
                                              num_hot, batch_epoch, seed):
    """The fragment-capable batch engine with ``fragment_exec=False``
    must remain bit-identical to the frozen pre-fragment engine across
    (protocol, lane count, window, contention, batch epoch) — the
    refactor is opt-in, not a behavior change."""
    wl = make_workload(
        WorkloadConfig(kind="ycsb", num_txns=256, num_records=10_000,
                       num_hot=num_hot, batch_epoch=batch_epoch, seed=seed)
    )
    sim = dict(max_rounds=1000, warmup_rounds=250, chunk_rounds=250,
               target_commits=10**9)
    kw = dict(PROTO_KW[protocol], n_exec=n_exec, window=window)
    results = []
    for layout in ("packed", "legacy"):
        cfg = EngineConfig(protocol=protocol, fragment_exec=False,
                           state_layout=layout, **kw, **sim)
        results.append(run_simulation(cfg, wl))
    assert _fingerprint(results[0]) == _fingerprint(results[1])


def test_fragment_mode_vmapped_matches_serial():
    """The vmapped sweep driver must reproduce fragment-mode serial
    execution exactly (fragment plan arrays stack like txn plans).

    The two cells share a seed and differ only in hot-set size: QueCC's
    lane-granular fragment schedule depends only on the partition
    structure, so their plan shapes coincide and they genuinely share
    one vmapped program (asserted via group_cells)."""
    cfg = EngineConfig(protocol="quecc", fragment_exec=True,
                       **PROTO_KW["quecc"], **FRAG_SIM)
    wls = [
        make_workload(
            WorkloadConfig(kind="ycsb", num_txns=256, num_records=10_000,
                           num_hot=h, multipart_frac=1.0, num_partitions=8,
                           batch_epoch=64, seed=0)
        )
        for h in (8, 64)
    ]
    batched = sweep.run_cells([(cfg, w) for w in wls])
    assert [r.raw["group_cells"] for r in batched] == [2, 2]
    serial = [run_simulation(cfg, w) for w in wls]
    for b, s_res in zip(batched, serial):
        assert _fingerprint(b) == _fingerprint(s_res)


def test_slot_col_accessors():
    """The packed layout's named-column accessors read the same values
    the engine carries (spot-check: a fresh state has every tid == -1
    and every phase == EMPTY)."""
    import jax.numpy as jnp

    from repro.core import engine as engine_lib

    cfg = EngineConfig(protocol="deadlock_free", n_exec=4, **FAST)
    state = engine_lib._state0(cfg, num_records=16, T=cfg.n_slots, K=3)
    assert state["slots"].shape == (engine_lib.SLOT_F, cfg.n_slots)
    assert jnp.all(engine_lib.slot_col(state, engine_lib.C_TID) == -1)
    assert jnp.all(
        engine_lib.slot_col(state, engine_lib.C_PHASE) == engine_lib.EMPTY
    )
    assert not bool(
        engine_lib.slot_col_bool(state, engine_lib.C_COMMITTING).any()
    )
    assert len(engine_lib.SLOT_COLS) == engine_lib.SLOT_F
    assert len(engine_lib.BATCH_SLOT_COLS) == engine_lib.BATCH_SLOT_F


def test_run_cells_vmapped_matches_serial():
    """The vmapped multi-cell driver must reproduce serial execution
    exactly, including per-cell warmup/termination accounting."""
    cfg = EngineConfig(protocol="deadlock_free", n_exec=8, **FAST)
    wls = [
        make_workload(WorkloadConfig(kind="ycsb", num_txns=512,
                                     num_records=20_000, num_hot=h, seed=1))
        for h in (8, 64, 512)
    ]
    batched = sweep.run_cells([(cfg, w) for w in wls])
    # the three cells must actually have shared one vmapped program
    assert [r.raw["group_cells"] for r in batched] == [3, 3, 3]
    serial = [run_simulation(cfg, w) for w in wls]
    for b, s in zip(batched, serial):
        assert _fingerprint(b) == _fingerprint(s)


@pytest.mark.xdist_group("compile_cache")
def test_compile_cache_shared_across_cells():
    """Cells differing only in workload content (same shapes) must
    reuse one compiled runner; simulation budget is not part of the
    trace either.

    xdist_group: counts process-local runner-cache entries, so it is
    pinned to the same pytest-xdist worker as the cache-accounting
    tests in test_sweep_cache.py (--dist loadgroup)."""
    before = sweep.runner_cache_info()["entries"]
    for hot, rounds in ((16, 1000), (128, 1500)):
        cfg = EngineConfig(protocol="twopl_waitfor", n_exec=9,
                           max_rounds=rounds, warmup_rounds=500,
                           chunk_rounds=500, target_commits=10**9)
        wl = make_workload(
            WorkloadConfig(kind="ycsb", num_txns=512, num_records=20_000,
                           num_hot=hot, seed=2)
        )
        run_simulation(cfg, wl)
    assert sweep.runner_cache_info()["entries"] == before + 1


def test_warmup_subtracts_all_counters():
    """aborts_ollp and wasted_ops subtract the warmup snapshot exactly
    like commits/aborts_deadlock (they used to be reported raw)."""
    wl = make_workload(
        WorkloadConfig(kind="tpcc", num_txns=512, num_warehouses=4,
                       ollp_miss_prob=0.5, seed=4)
    )
    base = dict(max_rounds=2000, chunk_rounds=500, target_commits=10**9)
    cfg_raw = EngineConfig(protocol="deadlock_free", n_exec=8,
                           warmup_rounds=0, **base)
    cfg_warm = EngineConfig(protocol="deadlock_free", n_exec=8,
                            warmup_rounds=1000, **base)
    raw = run_simulation(cfg_raw, wl)
    warm = run_simulation(cfg_warm, wl)
    # the warmup window contains OLLP aborts, so the measured counts
    # must be strictly smaller than the full-run totals
    assert raw.aborts_ollp > 0
    assert warm.aborts_ollp < raw.aborts_ollp
    assert warm.wasted_ops < raw.wasted_ops
    assert warm.commits < raw.commits


# ---------------------------------------------------------------------------
# Parallel sweep driver (SweepMode): device-sharded cell axis, pipelined
# asynchronous host loop, per-cell early exit — every mode bit-identical
# to per-cell run_simulation and to the SERIAL_MODE reference driver.

# Finite commit target + small chunks so per-cell early exit actually
# triggers, at *different* chunk boundaries for different contention
# levels (heterogeneous groups are where early exit can go wrong).
EXIT_SIM = dict(max_rounds=2000, warmup_rounds=500, chunk_rounds=250,
                target_commits=60)

DRIVER_MODES = [
    sweep.SweepMode(devices=1, pipeline=0, early_exit=True),
    sweep.SweepMode(devices=1, pipeline=2, early_exit=True),
    # clamped to the local device count in-process; the genuinely
    # multi-device case runs in tests/test_sharding.py's subprocess
    sweep.SweepMode(devices=4, pipeline=1, early_exit=True),
]


@pytest.mark.parametrize("protocol", sorted(PROTO_KW))
def test_driver_modes_match_serial(protocol):
    """Early-exit-only, pipelined + early-exit, and sharded driver modes
    must all reproduce per-cell ``run_simulation`` — and the
    ``SERIAL_MODE`` group driver — bit-exactly, for every protocol, on a
    group whose cells hit ``target_commits`` at different boundaries."""
    cfg = EngineConfig(protocol=protocol, **PROTO_KW[protocol], **EXIT_SIM)
    wls = [
        make_workload(WorkloadConfig(kind="ycsb", num_txns=256,
                                     num_records=10_000, num_hot=h, seed=3))
        for h in (4, 64, 1024)
    ]
    cells = [(cfg, w) for w in wls]
    ref = [run_simulation(cfg, w) for w in wls]
    for mode in [sweep.SERIAL_MODE] + DRIVER_MODES:
        got = sweep.run_cells(cells, mode=mode)
        for g, r in zip(got, ref):
            assert _fingerprint(g) == _fingerprint(r), (protocol, mode)


@settings(max_examples=6, deadline=None)
@given(
    cell_kind=st.sampled_from(sorted(PROTO_KW)
                              + ["quecc_frag", "overload_backlog"]),
    devices=st.sampled_from([1, 4]),
    pipeline=st.sampled_from([0, 1, 3]),
    early_exit=st.booleans(),
    target=st.sampled_from([25, 10**9]),
    seed=st.integers(min_value=0, max_value=3),
)
def test_driver_modes_property(cell_kind, devices, pipeline, early_exit,
                               target, seed):
    """Randomized driver-mode conformance over every protocol plus a
    fragment-granular QueCC cell and a bounded-backlog overload cell:
    (devices, pipeline depth, early exit, finite-vs-unbounded commit
    target, seed) must never change a single counter vs per-cell
    ``run_simulation``."""
    sim = dict(max_rounds=1000, warmup_rounds=250, chunk_rounds=250,
               target_commits=target)
    if cell_kind == "quecc_frag":
        cfg = EngineConfig(protocol="quecc", fragment_exec=True,
                           **PROTO_KW["quecc"], **sim)
        wl_kw = dict(kind="ycsb", num_txns=256, num_records=10_000,
                     multipart_frac=1.0, num_partitions=8, batch_epoch=64,
                     seed=seed)
    elif cell_kind == "overload_backlog":
        cfg = EngineConfig(protocol="deadlock_free", n_exec=8,
                           epoch_interval_rounds=150,
                           admission_policy="bounded_backlog",
                           backlog_cap=32, **sim)
        wl_kw = dict(kind="ycsb", num_txns=512, num_records=10_000,
                     batch_epoch=64, seed=seed)
    else:
        cfg = EngineConfig(protocol=cell_kind, **PROTO_KW[cell_kind], **sim)
        wl_kw = dict(kind="ycsb", num_txns=256, num_records=10_000,
                     seed=seed)
    wls = [make_workload(WorkloadConfig(**wl_kw, num_hot=h))
           for h in (8, 512)]
    mode = sweep.SweepMode(devices=devices, pipeline=pipeline,
                           early_exit=early_exit)
    got = sweep.run_cells([(cfg, w) for w in wls], mode=mode)
    ref = [run_simulation(cfg, w) for w in wls]
    for g, r in zip(got, ref):
        assert _fingerprint(g) == _fingerprint(r), (cell_kind, mode)


def test_statics_group_merges_traced_value_sweeps():
    """Cells differing only in *traced* values (here the epoch-interval
    scalar of an open-arrival rate sweep) must share one vmapped
    program — and still match per-cell execution bit-exactly. This is
    the compile-sharing payoff the runner-cache key promises."""
    sim = dict(max_rounds=1500, warmup_rounds=300, chunk_rounds=300,
               target_commits=10**9)
    wl = make_workload(
        WorkloadConfig(kind="ycsb", num_txns=512, num_records=20_000,
                       num_hot=16, batch_epoch=64, seed=5)
    )
    cfgs = [EngineConfig(protocol="deadlock_free", n_exec=8,
                         epoch_interval_rounds=e, **sim)
            for e in (100, 300)]
    got = sweep.run_cells([(c, wl) for c in cfgs])
    assert [r.raw["group_cells"] for r in got] == [2, 2]
    ref = [run_simulation(c, wl) for c in cfgs]
    for g, r in zip(got, ref):
        assert _fingerprint(g) == _fingerprint(r)


def test_warmup_snapshot_off_grid_chunk_split():
    """``warmup_rounds`` not a multiple of ``chunk_rounds``: the chunk
    containing it is split at the warmup boundary, so the snapshot is
    taken exactly at ``warmup_rounds`` — bit-identical to running the
    same budget on a chunk grid that contains the boundary natively.
    (Previously the snapshot silently landed at the last smaller chunk
    boundary, shifting every warmup-subtracted counter.)"""
    wl = make_workload(
        WorkloadConfig(kind="ycsb", num_txns=512, num_records=20_000,
                       num_hot=8, seed=1)
    )
    base = dict(protocol="deadlock_free", n_exec=8, max_rounds=2000,
                warmup_rounds=750, target_commits=10**9)
    split = run_simulation(EngineConfig(**base, chunk_rounds=500), wl)
    on_grid = run_simulation(EngineConfig(**base, chunk_rounds=250), wl)
    assert _fingerprint(split) == _fingerprint(on_grid)
    # the schedule inserts exactly one off-grid boundary, then returns
    # to the original chunk grid
    cfg = EngineConfig(**base, chunk_rounds=500)
    assert list(sweep.chunk_boundaries(cfg)) == [500, 750, 1000, 1500, 2000]


# ---------------------------------------------------------------------------
# Scheduled family conformance sweep. ``scheduled`` stays out of PROTO_KW
# on purpose: the frozen legacy engine (state_layout="legacy") predates
# the family, so there is no legacy differential — its contract is
# leap/dense, vmap/serial, driver-mode, and K-dispatch bit-identity
# against itself, plus the golden fixtures in tests/test_golden_traces.py.

SCHED_KW = dict(n_exec=8)


def _run_scheduled(wl, *, leap, sim=FAST, **kw):
    cfg = EngineConfig(protocol="scheduled", event_leap=leap,
                       **dict(SCHED_KW, **kw), **sim)
    return run_simulation(cfg, wl)


@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("release_path", ["csr", "dense"])
def test_scheduled_leap_matches_dense(ycsb_hot, k, release_path):
    """Cluster-chain execution must leap bit-identically to its dense
    round loop, across K-fused dispatch and both release paths."""
    kw = dict(rounds_per_dispatch=k, release_path=release_path)
    leap = _run_scheduled(ycsb_hot, leap=True, **kw)
    dense = _run_scheduled(ycsb_hot, leap=False, **kw)
    assert _fingerprint(leap) == _fingerprint(dense)
    assert leap.raw["steps_executed"] <= dense.raw["steps_executed"]
    # the family never aborts: per-cluster total orders, no lock table
    assert leap.aborts_deadlock == 0 and leap.aborts_ollp == 0


def test_scheduled_leap_actually_skips_rounds(ycsb_hot):
    """Cluster chains serialize on lanes, so most rounds are barrier or
    chain waits — the leap must skip a large fraction of them."""
    res = _run_scheduled(ycsb_hot, leap=True)
    assert res.raw["steps_executed"] < 0.7 * res.raw["rounds_total"]


SCHED_GRID = [
    # (num_hot, hot_per_txn, n_exec, batch_epoch, k, seed)
    (0, 2, 8, 64, 1, 0),
    (4, 1, 2, 64, 8, 1),
    (64, 2, 8, 256, 8, 2),
    (512, 1, 16, 256, 1, 3),
    (8, 2, 6, 100, 8, 0),
]


def _check_scheduled_leap_dense(num_hot, hot_per_txn, n_exec, batch_epoch,
                                k, seed):
    wl = make_workload(
        WorkloadConfig(kind="ycsb", num_txns=256, num_records=10_000,
                       num_hot=num_hot, hot_per_txn=hot_per_txn,
                       batch_epoch=batch_epoch, seed=seed)
    )
    sim = dict(max_rounds=1000, warmup_rounds=250, chunk_rounds=250,
               target_commits=10**9)
    kw = dict(n_exec=n_exec, rounds_per_dispatch=k)
    leap = _run_scheduled(wl, leap=True, sim=sim, **kw)
    dense = _run_scheduled(wl, leap=False, sim=sim, **kw)
    assert _fingerprint(leap) == _fingerprint(dense)


@pytest.mark.parametrize(
    "num_hot,hot_per_txn,n_exec,batch_epoch,k,seed", SCHED_GRID)
def test_scheduled_leap_matches_dense_grid(num_hot, hot_per_txn, n_exec,
                                           batch_epoch, k, seed):
    _check_scheduled_leap_dense(num_hot, hot_per_txn, n_exec, batch_epoch,
                                k, seed)


@settings(max_examples=8, deadline=None)
@given(
    num_hot=st.sampled_from([0, 4, 64, 512]),
    hot_per_txn=st.sampled_from([1, 2]),
    n_exec=st.sampled_from([2, 6, 16]),
    batch_epoch=st.sampled_from([64, 100, 256]),
    k=st.sampled_from([1, 8]),
    seed=st.integers(min_value=0, max_value=3),
)
def test_scheduled_leap_matches_dense_property(num_hot, hot_per_txn, n_exec,
                                               batch_epoch, k, seed):
    """Randomized conformance over (contention, hot fan-out, lanes,
    batch epoch, dispatch fusion, seed) — the axes fig18 sweeps."""
    _check_scheduled_leap_dense(num_hot, hot_per_txn, n_exec, batch_epoch,
                                k, seed)


def test_scheduled_vmapped_matches_serial():
    """The vmapped sweep driver must reproduce scheduled serial
    execution exactly; two same-shape cells (same config, seeds picked
    so the cluster plans land in the same pow2 buckets) genuinely share
    one vmapped program."""
    cfg = EngineConfig(protocol="scheduled", **SCHED_KW, **FAST)
    wls = [
        make_workload(
            WorkloadConfig(kind="ycsb", num_txns=512, num_records=20_000,
                           num_hot=8, seed=s)
        )
        for s in (0, 1)
    ]
    batched = sweep.run_cells([(cfg, w) for w in wls])
    assert [r.raw["group_cells"] for r in batched] == [2, 2]
    serial = [run_simulation(cfg, w) for w in wls]
    for b, s_res in zip(batched, serial):
        assert _fingerprint(b) == _fingerprint(s_res)


def test_scheduled_driver_modes_match_serial():
    """Early-exit, pipelined, and sharded driver modes reproduce
    per-cell execution for the scheduled family on a heterogeneous
    group (cells hit ``target_commits`` at different boundaries)."""
    cfg = EngineConfig(protocol="scheduled", **SCHED_KW, **EXIT_SIM)
    wls = [
        make_workload(WorkloadConfig(kind="ycsb", num_txns=256,
                                     num_records=10_000, num_hot=h, seed=3))
        for h in (4, 64, 1024)
    ]
    cells = [(cfg, w) for w in wls]
    ref = [run_simulation(cfg, w) for w in wls]
    for mode in [sweep.SERIAL_MODE] + DRIVER_MODES:
        got = sweep.run_cells(cells, mode=mode)
        for g, r in zip(got, ref):
            assert _fingerprint(g) == _fingerprint(r), mode
