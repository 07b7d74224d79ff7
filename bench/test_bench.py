"""Self-checks of the benchmark, on the CPU at sizes a test run holds.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python3 -m pytest -q -p no:cacheprovider bench/test_bench.py

* each kind of stream's generator gives the program's arrays, and a
  kind is found as a file of ``bench/gen/`` with no other change;
* the trace reduction's arithmetic, on a trace written by hand, and the
  in-memory recorder's cut at its cap;
* each kernel's bytes-per-call function against the shapes the engine
  passes to the kernel (Pallas interpreter);
* the reference equals the program on every protocol, and the control
  (the reference with isolation switched off) does not;
* the reference's waits-for relation has FIFO queue edges: it breaks the
  smallest deadlock closed by a read queued behind a waiting writer, and
  commits in every chunk of a mixed read/write stream under each dynamic
  2PL scheme (where the program, lacking that edge, differs: a strict
  expected failure);
* the harness run end to end at a small size is correct, and each fault
  planted under it (a runner that returns its state unchanged, half of a
  cell's transaction stream left out, a counter altered where it is
  produced, a compile inside the window, and on the four-chip cell the
  exchange between chips left out) makes it come out not correct.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import gen, run, trace  # noqa: E402
from bench.reference import core as ref  # noqa: E402
from bench.reference.data import MODE_READ, MODE_WRITE, Workload  # noqa: E402

SMALL_BUDGET = dict(max_rounds=600, warmup_rounds=200, chunk_rounds=200,
                    target_commits=100_000_000)


# the fig13 contention sweep's traffic on four chips, held out of
# BENCHMARK.json until its set-up is the same work on every seed
# (PERF.md, open questions); its files and the harness's multi-chip path
# are checked here
FIG13 = dict(name="ycsb10m_40c.fig13_hot_sweep", config="ycsb10m_40c",
             traffic="fig13_hot_sweep", chips=4)


def small_cell(name, txns: int = 512, pool: int = 2) -> run.Cell:
    """The cell as BENCHMARK.json defines it, or of a workload entry
    (a dict) under it, cut to a test's size."""
    if isinstance(name, dict):
        cell = run.make_cell(
            run._load_json(os.path.join(ROOT, "BENCHMARK.json")), name)
    else:
        cell = run.load_cell(name)
    cell.config = json.loads(json.dumps(cell.config))
    cell.traffic = json.loads(json.dumps(cell.traffic))
    cell.config["workload"]["num_txns"] = txns
    cell.config[cell.traffic["budget"]] = dict(SMALL_BUDGET)
    cell.traffic["pool_calls"] = pool
    return cell


# ------------------------------------------------------------- generator
GEN_CASES = [(kind, case) for kind in gen.kinds()
             for case in gen.load(kind).CASES]


@pytest.mark.parametrize("kind,case", GEN_CASES, ids=[
    f"{k}-" + "-".join(str(v) for v in c.values()) for k, c in GEN_CASES])
def test_generator_matches_program(kind, case):
    from repro.core.workloads import WorkloadConfig, make_workload

    kw = dict(case, kind=kind)
    want = make_workload(WorkloadConfig(**kw))
    got = gen.make_workload(kw)
    for k in ("keys", "modes", "nkeys", "part", "exec_ops", "ollp",
              "ollp_miss"):
        a, b = getattr(got, k), getattr(want, k)
        assert a.dtype == b.dtype and np.array_equal(a, b), k
    assert got.num_records == want.num_records


def test_unknown_kind_or_field_is_an_error():
    with pytest.raises(ValueError, match="'nosuchkind'"):
        gen.make_workload(dict(kind="nosuchkind", num_txns=8))
    with pytest.raises(ValueError, match="'no such'"):
        gen.make_workload(dict(kind="no such", num_txns=8))
    with pytest.raises(TypeError, match="'num_hott'"):
        gen.make_workload(dict(kind="ycsb", num_txns=8, num_hott=4))
    # a field of another kind is unknown to this one
    with pytest.raises(TypeError, match="'num_warehouses'"):
        gen.make_workload(dict(kind="ycsb", num_txns=8, num_warehouses=4))


# a kind of stream written as a file of its own: every txn writes the
# same `width` consecutive keys from a random start
KIND_FILE = """
import dataclasses

import numpy as np

from bench.gen import Common
from bench.reference.data import MODE_WRITE, Workload


@dataclasses.dataclass(frozen=True)
class Params(Common):
    num_records: int = 1000
    width: int = 3


CASES = []


def generate(p):
    rng = np.random.default_rng(p.seed)
    start = rng.integers(0, p.num_records - p.width, size=p.num_txns)
    keys = (start[:, None] + np.arange(p.width)).astype(np.int32)
    n, k = keys.shape
    return Workload(
        keys=keys, modes=np.full((n, k), MODE_WRITE, np.int32),
        nkeys=np.full(n, k, np.int32), part=keys % 16,
        exec_ops=np.full(n, k, np.int32), ollp=np.zeros(n, bool),
        ollp_miss=np.zeros(n, bool), num_records=p.num_records,
        batch_epoch=p.batch_epoch)
"""


@pytest.fixture
def kind_as_file(tmp_path, monkeypatch):
    """A kind module ``runs`` placed as a file in a directory of
    ``bench.gen``'s package path, with no other change."""
    import importlib

    (tmp_path / "runs.py").write_text(KIND_FILE)
    monkeypatch.setattr(gen, "__path__", [*gen.__path__, str(tmp_path)])
    monkeypatch.delitem(sys.modules, "bench.gen.runs", raising=False)
    importlib.invalidate_caches()
    yield "runs"
    sys.modules.pop("bench.gen.runs", None)


def test_kind_placed_as_a_file_is_found(kind_as_file):
    assert kind_as_file in gen.kinds()
    w = gen.make_workload(dict(kind=kind_as_file, num_txns=64, seed=2**33,
                               width=4))
    assert w.keys.shape == (64, 4) and w.num_records == 1000
    assert np.all(np.diff(w.keys, axis=1) == 1)
    with pytest.raises(TypeError, match="'num_hot'"):
        gen.make_workload(dict(kind=kind_as_file, num_hot=4))
    # the harness builds a cell's pool from it with no change of its own
    cell = small_cell("ycsb10m_40c.waitdie_hot64", txns=32, pool=2)
    cell.config["workload"] = dict(kind=kind_as_file, num_txns=32,
                                   num_records=500)
    cell.traffic["mix"] = {"width": 5}
    pool = run.build_pool(cell, 11)
    assert [c.streams[0].keys.shape for c in pool] == [(32, 5)] * 2
    assert not np.array_equal(pool[0].streams[0].keys,
                              pool[1].streams[0].keys)


def test_fig13_pool_is_the_contention_sweep():
    from bench.reference import plans

    cell = small_cell(FIG13, txns=64, pool=2)
    assert cell.chips == 4
    pool = run.build_pool(cell, 2**32 + 5)
    assert len(pool) == 2
    for call in pool:
        assert len(call.streams) == 3 and len(call.cells) == 24
        assert [c.stream for c in call.cells] == [0] * 8 + [1] * 8 + [2] * 8
        for c in call.cells:
            e = c.engine
            assert e["n_exec"] + e.get("n_cc", 0) == 40
            assert e["max_rounds"] == SMALL_BUDGET["max_rounds"]
            plans.load(c.protocol)
        # the three hot sets, each a stream of its own
        hot = [int((w.keys < 1024).sum(axis=1).min()) for w in call.streams]
        assert hot == [2, 2, 2]
        assert [int(w.keys[:, :2].max()) < n for w, n in
                zip(call.streams, (1024, 64, 16))] == [True] * 3
    assert len({c.protocol for c in pool[0].cells}) == 8
    # the check's sample takes one cell of each stream, on any seed
    for seed in (1, 2**32 + 5, 4000000039):
        picked = run.sample(pool, seed, 4)
        assert len(set(picked)) == 4 and len({ci for ci, _ in picked}) == 1
        assert {pool[ci].cells[i].stream for ci, i in picked} == {0, 1, 2}


# ------------------------------------------------------- trace reduction
def _ev(name, start, dur):
    return trace.Event(name, start, dur)


def hand_trace() -> trace.Trace:
    """Two chips, a window [100, 1100) ns, host spans and Python frames."""
    dev0 = {
        "XLA Ops": [_ev("fusion.1", 50, 100),  # clipped to [100, 150)
                    _ev("fusion.2", 120, 80),  # overlaps: union to 200
                    _ev("%dep_wavefront.11 = (s32[8,128]) custom-call("
                        "%a, %b)", 400, 100),
                    # takes the kernel's output as an operand: not the kernel
                    _ev("%fusion.4 = s32[8,128] fusion(%dep_wavefront.11)",
                        500, 0),
                    _ev("fusion.1", 900, 300)],  # clipped to 1100
        "XLA Modules": [_ev("jit_run_chunk(1)", 100, 100),
                        _ev("jit_run_chunk(1)", 400, 100),
                        _ev("jit_other", 900, 200)],
    }
    dev1 = {"XLA Ops": [_ev("fusion.3", 300, 200)],
            "XLA Modules": [_ev("jit_run_chunk(2)", 300, 200)]}
    host = {"python3": [
        _ev("bench.window", 100, 1000),
        _ev("bench.call", 100, 500),
        _ev("$engine.py:2827 make_plan", 200, 150),
        _ev("$planner.py:146 plan_dgcc", 210, 100),  # nested: not counted
        _ev("$engine.py:2827 make_plan", 620, 50),
        _ev("$other.py:1 make_plan", 700, 999),  # another file
        _ev("bench.call", 600, 400),
    ]}
    return trace.Trace(devices={"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                       host=host, lo=100, hi=1100)


def test_trace_reduction_by_hand():
    tr = hand_trace()
    # chip 0: [100, 200) + [400, 500) + [900, 1100) = 400 ns busy
    assert trace.busy_ns(tr, "/device:TPU:0") == 400
    assert trace.busy_ns(tr, "/device:TPU:1") == 200
    plans = trace.frames(tr, "make_plan", "engine.py")
    assert [e.dur for e in plans] == [150, 50]
    # the other file's frame ends past the window
    assert [e.dur for e in trace.frames(tr, "make_plan")] == [150, 50]
    by = trace.sum_by_name(trace.in_window(tr, tr.ops("/device:TPU:0")),
                           short=True)
    assert by == {"fusion.2": 80, "dep_wavefront.11": 100, "fusion.1": 300,
                  "fusion.4": 0}
    # gaps of chip 0: [200, 400) and [500, 900), longest first
    assert trace.gaps(tr.ops("/device:TPU:0"), tr.lo, tr.hi) == [
        (500, 900), (200, 400)]
    # the gap [500, 900) is covered by the second call span for 300 of
    # its 400 ns, more than by any other host span
    assert trace.idle_gaps(tr, "/device:TPU:0")[0] == ["bench.call", 4e-7]
    assert trace.union_ns([_ev("a", 0, 10), _ev("b", 5, 10),
                           _ev("c", 30, 5)], 0, 100) == 20


def test_recorder_cuts_the_trace_at_its_cap():
    f = jax.jit(lambda x: (x @ x).sum())
    x = np.ones((128, 128), np.float32)
    f(x).block_until_ready()
    rec = trace.Recorder(cap_s=0.2)
    t0 = time.perf_counter()
    while not rec.stopped:
        with jax.profiler.TraceAnnotation("bench.call"):
            f(x).block_until_ready()
    tr = rec.finish()
    took = time.perf_counter() - t0
    assert 0.15e9 < tr.hi - tr.lo <= took * 1e9
    names = {e.name for evs in tr.host.values() for e in evs}
    assert {"bench.mark", "bench.call"} <= names


def test_metric_readers_by_hand():
    from bench.metrics import device_idle, dep_wavefront_roofline, step_us

    tr = hand_trace()
    cell = small_cell("ycsb10m_40c.dgcc_hot64")
    meta = type("M", (), {"pred_width": 3, "max_keys": 10})()
    c = run.SimCell(engine={}, stream=0, protocol="dgcc", slots=128,
                    meta=meta)
    res = type("R", (), {"raw": {"steps_executed": 4, "group_cells": 1}})()
    call = run.Call(seeds=[0], streams=[None], cells=[c], start_ns=100,
                    end_ns=1100, results=[res])
    ctx = run.TraceContext(tr, cell, [call], [0, 1],
                           run.peaks_of("TPU v5 lite"))
    # chip 0 idle 600/1000, chip 1 idle 800/1000
    assert device_idle.read(ctx) == pytest.approx(70.0)
    # a cell of four chips, two of them with no plane: idle throughout
    ctx = run.TraceContext(tr, cell, [call], [0, 1, 2, 3],
                           run.peaks_of("TPU v5 lite"))
    assert device_idle.read(ctx) == pytest.approx(85.0)
    # iterations are counted for a call on one chip only
    assert step_us.read(ctx) is None
    ctx = run.TraceContext(tr, cell, [call], [0],
                           run.peaks_of("TPU v5 lite"))
    # chip 0's runner time 100 + 100 ns over 4 iterations
    assert step_us.read(ctx) == pytest.approx(0.05)
    # one kernel event of 100 ns; the fusion that reads its output is not
    want = (dep_wavefront_roofline.bytes_per_call(128, 3) / 819e9
            / 100e-9 * 100)
    assert dep_wavefront_roofline.read(ctx) == pytest.approx(want)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit, match="no peaks"):
        run.peaks_of("TPU v99")
    assert run.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# ------------------------------------------------------ kernel shapes
def _record_kernel(monkeypatch, module, attr):
    """Wrap a kernel entry so every trace of it records its arrays."""
    import importlib

    mod = importlib.import_module(module)
    real = getattr(mod, attr)
    seen = []

    def wrapped(*args, **kw):
        out = real(*args, **kw)
        # compiled for the TPU, every tile (bool ones too) is s32
        arrays = list(args) + list(out)
        seen.append(sum(int(np.prod(a.shape)) * 4 for a in arrays))
        return out

    monkeypatch.setattr(mod, attr, wrapped)
    return seen


@pytest.mark.parametrize("name,module,attr", [
    ("ycsb10m_40c.dgcc_hot64", "repro.kernels.dep_wavefront.ops",
     "dep_wavefront_kernel"),
    ("tpcc_w4_80c.orthrus", "repro.kernels.lock_grant.ops",
     "lock_grant_kernel"),
])
def test_kernel_bytes_match_engine_shapes(monkeypatch, name, module, attr):
    import importlib

    from repro.core import engine as engine_lib
    from repro.core import sweep

    metric = {"dep_wavefront_kernel": "dep_wavefront_roofline",
              "lock_grant_kernel": "lock_grant_roofline"}[attr]
    reader = importlib.import_module(f"bench.metrics.{metric}")
    cell = small_cell(name, txns=256)
    call = run.build_pool(cell, 3)[0]
    run.plan_shapes(call, cell)
    seen = _record_kernel(monkeypatch, module, attr)
    cfg, wl = run.call_args(call, cell, dict(max_rounds=8, warmup_rounds=0,
                                             chunk_rounds=8))[0]
    cfg = dataclasses.replace(cfg, kernel_impl="pallas")
    plan = engine_lib.make_plan(cfg, wl)
    sweep.simulate_plans(cfg, [plan], mode=sweep.SERIAL_MODE)
    assert seen, "the engine never called the kernel"
    assert set(seen) == {reader.call_bytes(call.cells[0])}


# ---------------------------------------------- reference and control
REF_CASES = [
    (dict(kind="ycsb", num_hot=64), dict(protocol="twopl_waitdie", n_exec=8)),
    (dict(kind="ycsb", num_hot=16), dict(protocol="twopl_waitfor", n_exec=8)),
    (dict(kind="ycsb", num_hot=16),
     dict(protocol="twopl_dreadlocks", n_exec=8)),
    (dict(kind="ycsb", num_hot=64), dict(protocol="deadlock_free", n_exec=8)),
    (dict(kind="ycsb", num_hot=64),
     dict(protocol="partitioned_store", n_exec=8)),
    (dict(kind="ycsb", num_hot=64),
     dict(protocol="dgcc", n_cc=2, n_exec=8, window=4)),
    (dict(kind="ycsb", num_hot=64),
     dict(protocol="quecc", n_cc=2, n_exec=8, window=4)),
    (dict(kind="tpcc", num_warehouses=4),
     dict(protocol="orthrus", n_cc=4, n_exec=16, window=4)),
]


@pytest.mark.parametrize("wkw,ekw", REF_CASES,
                         ids=[e["protocol"] for _, e in REF_CASES])
def test_reference_matches_program_and_control_does_not(wkw, ekw):
    from repro.core import sweep
    from repro.core.engine import EngineConfig
    from repro.core.workloads import WorkloadConfig, make_workload

    kw = dict(wkw, num_txns=1024, seed=2**31 + 9)
    w = gen.make_workload(kw)
    res = sweep.run_cells([(EngineConfig(**ekw, **SMALL_BUDGET),
                            make_workload(WorkloadConfig(**kw)))])[0]
    want = ref.simulate(ref.RefConfig(**ekw, **SMALL_BUDGET), w)
    got = run.fingerprint(res)
    assert run.mismatches(got, want) == []
    control = ref.simulate(
        ref.RefConfig(**ekw, **SMALL_BUDGET, isolation=False), w)
    assert run.mismatches(control, want) != []


# The waits-for relation under FIFO grants: a read queued behind a
# waiting writer waits for that writer, though every holder of its
# record reads.
def fifo_deadlock():
    """The smallest FIFO deadlock. Admitted in slot order, so the
    timestamps run W < H < R: H read-holds x; W's write on x waits for H;
    R, which read-holds y, queues a read on x behind W; H's write on y
    waits for R. Only R's edge to the queued W closes W -> H -> R -> W,
    and no edge to a holder points at an older transaction."""
    rd, wr = MODE_READ, MODE_WRITE
    x, y, z = 1, 2, 0
    keys = np.array([[z, x], [x, y], [y, x]], np.int32)  # W, H, R
    modes = np.array([[wr, wr], [rd, wr], [rd, rd]], np.int32)
    n = len(keys)
    return Workload(keys=keys, modes=modes, nkeys=np.full(n, 2, np.int32),
                    part=keys % 16, exec_ops=np.full(n, 2, np.int32),
                    ollp=np.zeros(n, bool), ollp_miss=np.zeros(n, bool),
                    num_records=16, batch_epoch=512)


DYNAMIC_2PL = ["twopl_waitdie", "twopl_waitfor", "twopl_dreadlocks"]


@pytest.mark.parametrize("protocol", DYNAMIC_2PL)
def test_fifo_deadlock_aborts_the_read_behind_a_writer(protocol):
    w = fifo_deadlock()
    cfg = ref.RefConfig(protocol=protocol, n_exec=3, **SMALL_BUDGET)
    loop = ref._LockLoop(cfg, ref.make_plan(cfg, w))
    for r in range(1, 65):
        loop.run_to(r)
        if int(loop.state["aborts_dl"]):
            break
    assert int(loop.state["aborts_dl"]) == 1, "the deadlock is never broken"
    # R (slot 2): wait-die on its edge to the older W; wait-for and
    # dreadlocks as the youngest member of the cycle
    phase = np.asarray(loop.state["phase"])
    aborted = np.isin(phase, (ref.REL, ref.BACKOFF)) & ~np.asarray(
        loop.state["committing"])
    assert aborted.tolist() == [False, False, True]
    assert ref.simulate(cfg, w)["commits"] > 0


# the mixed-mode stream: 48 hot records, each txn reading the keys of its
# own parity and writing the rest, at 40 lanes; a budget in which
# wait-for and dreadlocks without the queue edge stop committing
MIXED = dict(kind="ycsb", num_txns=512, num_records=20_000, num_hot=48,
             seed=0)
MIXED_BUDGET = dict(max_rounds=8000, warmup_rounds=1000, chunk_rounds=1000,
                    target_commits=100_000_000)


def mixed_modes(w):
    parity = (w.keys + np.arange(len(w.keys))[:, None]) % 2
    return dataclasses.replace(w, modes=np.where(
        parity == 0, MODE_READ, MODE_WRITE).astype(w.modes.dtype))


@pytest.fixture(scope="module")
def mixed_stream():
    return mixed_modes(gen.make_workload(MIXED))


@pytest.mark.parametrize("protocol", DYNAMIC_2PL)
def test_reference_commits_on_mixed_modes(mixed_stream, protocol):
    cfg = ref.RefConfig(protocol=protocol, n_exec=40, **MIXED_BUDGET)
    loop = ref._LockLoop(cfg, ref.make_plan(cfg, mixed_stream))
    commits = [0]
    for b in ref.chunk_boundaries(cfg):
        loop.run_to(b)
        commits.append(int(loop.state["commits"]))
    assert np.all(np.diff(commits) > 0), commits
    want = ref.simulate(cfg, mixed_stream)
    assert want["aborts_deadlock"] > 0
    control = ref.simulate(dataclasses.replace(cfg, isolation=False),
                           mixed_stream)
    assert run.mismatches(control, want) != []


PROGRAM_LACKS_QUEUE_EDGE = (
    "the program's waits-for relation (src/repro/core/engine.py:1527-1557, "
    "adj in stage.deadlock) links a waiter only to conflicting holders: a "
    "read queued behind a waiting writer has no edge, so the cycle it "
    "closes is never broken")


@pytest.mark.parametrize("protocol", [
    pytest.param(p, marks=pytest.mark.xfail(
        strict=True, raises=AssertionError, reason=PROGRAM_LACKS_QUEUE_EDGE))
    for p in DYNAMIC_2PL])
def test_reference_matches_program_on_mixed_modes(mixed_stream, protocol):
    from repro.core import sweep
    from repro.core.engine import EngineConfig
    from repro.core.workloads import WorkloadConfig, make_workload

    wl = mixed_modes(make_workload(WorkloadConfig(**MIXED)))
    ekw = dict(protocol=protocol, n_exec=40, **MIXED_BUDGET)
    res = sweep.run_cells([(EngineConfig(**ekw), wl)])[0]
    want = ref.simulate(ref.RefConfig(**ekw), mixed_stream)
    assert run.mismatches(run.fingerprint(res), want) == []


# ------------------------------------------- the harness, end to end
def _run(cell, seed=2**31 + 3, seconds=0.5):
    counter = run.CompileCounter()
    devices = jax.devices()[:cell.chips]
    return run.run(cell, seed, seconds, False, devices, counter)


def test_harness_small_cell_is_correct():
    cell = small_cell("ycsb10m_40c.waitdie_hot64")
    out = _run(cell)
    assert out["correct"] and out["attempted"] >= 1
    assert out["window_compiles"] == 0
    assert list(out["metrics"]) == ["cell_s", "setup_s"]
    assert list(out)[-1] == "checks"


def test_harness_four_chip_cell_is_correct():
    cell = small_cell(FIG13, txns=256, pool=1)
    if cell.chips > len(jax.devices()):
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_"
                    f"count={cell.chips}")
    out = _run(cell, seconds=0.01)
    assert out["correct"] and out["attempted"] == 24
    assert out["window_compiles"] == 0
    # one checked cell per chip of the cell, one of each stream among
    # them: a sharded group puts stream s on chip s
    assert len(out["checked"]) == 4
    assert {c["stream"] for c in out["checked"]} == {0, 1, 2}


def _fault_runner_unchanged(monkeypatch):
    from repro.core import sweep

    ident = jax.jit(lambda p, s, r_end: s)
    monkeypatch.setattr(sweep, "get_runner", lambda *a, **k: ident)


def _fault_half_left_out(monkeypatch):
    from repro.core import engine as engine_lib

    real = engine_lib.make_plan

    def first_half(cfg, wl):
        n = len(wl.nkeys) // 2
        return real(cfg, dataclasses.replace(wl, **{
            k: getattr(wl, k)[:n] for k in ("keys", "modes", "nkeys", "part",
                                            "exec_ops", "ollp",
                                            "ollp_miss")}))

    monkeypatch.setattr(engine_lib, "make_plan", first_half)


def _fault_counter_altered(monkeypatch):
    from repro.core import sweep

    real = sweep._GroupRun.finish

    def altered(self, time_sink=None):
        out = real(self, time_sink)
        for res in out:
            res.raw["steps_executed"] += 1
        return out

    monkeypatch.setattr(sweep._GroupRun, "finish", altered)


def _fault_chips_left_out(monkeypatch):
    from repro.core import sweep

    real = sweep._counters_to_host

    def first_chip(snap, n):
        # only the first chip's shard of each counter comes back to the
        # host, and stands for every chip's
        def local(v):
            shards = getattr(v, "addressable_shards", None) or []
            if len(shards) < 2 or shards[0].data.shape == v.shape:
                return v
            x = np.asarray(shards[0].data)
            return np.concatenate([x] * len(shards))

        return real({k: local(v) for k, v in snap.items()}, n)

    monkeypatch.setattr(sweep, "_counters_to_host", first_chip)


FAULTS = {
    "runner_unchanged": ("ycsb10m_40c.dgcc_hot64", _fault_runner_unchanged),
    "half_left_out": ("ycsb10m_40c.waitdie_hot64", _fault_half_left_out),
    "counter_altered": ("tpcc_w4_80c.orthrus", _fault_counter_altered),
    # the four-chip cell, with the exchange between its chips
    "fig13-runner_unchanged": (FIG13, _fault_runner_unchanged),
    "fig13-half_left_out": (FIG13, _fault_half_left_out),
    "fig13-chips_left_out": (FIG13, _fault_chips_left_out),
    "fig13-counter_altered": (FIG13, _fault_counter_altered),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(monkeypatch, fault):
    name, plant = FAULTS[fault]
    cell = small_cell(name, txns=256, pool=1)
    if cell.chips > len(jax.devices()):
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_"
                    f"count={cell.chips}")
    plant(monkeypatch)
    out = _run(cell, seconds=0.01)
    assert not out["correct"]
    assert out["checks"]["mismatches"]["value"] > 0


def test_compile_inside_the_window_is_not_correct(monkeypatch):
    from repro.core import sweep

    cell = small_cell("ycsb10m_40c.waitdie_hot64", txns=256, pool=1)
    real = sweep.get_runner
    # a runner built afresh for every group the window runs, as one for a
    # shape that set-up did not warm would be
    def fresh(*a, **k):
        runner = real(*a, **k)
        return jax.jit(lambda *x: runner(*x))

    monkeypatch.setattr(sweep, "get_runner", fresh)
    out = _run(cell, seconds=0.01)
    assert out["checks"]["window_compiles"]["value"] > 0
    assert not out["correct"]
