"""Chip benchmark of the OLTP simulator: one cell of BENCHMARK.json, once.

    python3 bench/run.py --workload <config>.<traffic> \
        --seed 7 --seconds 10 --trace 0

Run from the root of a checkout; the script puts ``src/`` and the root
on the path itself and does everything in this one process, which holds
the chip. No TPU, or fewer chips than the cell asks for, is an error.

A cell names a configuration (``bench/configs/<config>.json``: the
deployment, its budgets and guarantees; its ``workload.kind`` names the
stream's generator, ``bench/gen/<kind>.py``) and a traffic mix
(``bench/traffic/<traffic>.json``: the mix's parameters, the streams and
protocols of one call and the pool size). Per-layer metrics are read by
``bench/metrics/<name>.py``, and the reference plans a protocol with
``bench/reference/plans/<protocol>.py``. Nothing here names a cell, a
kind of stream, a mix, a protocol or a metric: a new deployment is
files (a kind module where its stream is of a new kind, a configuration,
a traffic file) and entries in BENCHMARK.json.

One run:

1. set-up (``setup_s``): generate a pool of calls from ``--seed`` with
   the benchmark's own generators (``bench/gen/``); plan each with the
   program's ``engine.make_plan`` to learn its plan shapes
   (``engine.plan_meta``), and warm every distinct call shape through
   ``repro.core.sweep.run_cells`` on a two-round budget, so the
   compiled runners (from the persistent cache in ``.bench_cache/jax``)
   and every eager program of the host loop exist before the window;
2. window: a closed loop of ``sweep.run_cells`` calls, one pool entry
   each, until ``--seconds`` have passed; the running call finishes and
   counts. ``cell_s`` is the window's seconds over the simulation cells
   completed (a cell is complete when its counters are on the host).
   Compiles inside the window are counted; a run with any is not correct;
3. correctness, once the window has closed: a sample of the completed
   cells (``CHECK_CELLS`` per chip of the cell), drawn from the seed, is
   simulated again by the reference (``bench/reference``), which imports
   nothing of the program, and every counter the cell reads back is
   compared exactly;
4. with ``--trace 1`` the window runs under the profiler (in memory),
   shortened to ``TRACE_SECONDS`` (whole calls still) and cut at
   ``TRACE_CAP_SECONDS``, and the cell's per-layer metrics are read from
   the trace instead.

The last line of standard output is the result as JSON.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE = os.path.join(ROOT, ".bench_cache")
# a two-chunk budget of one round each, which warms a call's runners and
# host-loop programs: the second chunk feeds a runner its own output
# state, whose sharding differs from the state set-up places
WARM_BUDGET = dict(max_rounds=2, warmup_rounds=0, chunk_rounds=1)
# the sweep mode users get by default on the cell's chips, stated here so
# that no REPRO_SWEEP_* variable in the environment changes it
SWEEP_MODE = dict(pipeline=1, early_exit=True)
# simulation cells checked against the reference, from one window call,
# per chip of the cell: a call on several chips spreads its cells over
# them, and a fault of one chip's share shows only in its cells
CHECK_CELLS = 1
# the traced run holds every device op (about a million events per
# chip-second): whole calls up to TRACE_SECONDS, never past the cap
TRACE_SECONDS = 2.0
TRACE_CAP_SECONDS = 10.0
# the numbers compared, with their limits: counters compared exactly,
# and no compile inside the measured window
LIMITS = {"mismatches": 0, "window_compiles": 0}


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of BENCHMARK.json's workloads, with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def budget(self) -> dict:
        return self.config[self.traffic["budget"]]


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    return make_cell(spec, work[name], root)


def make_cell(spec: dict, w: dict, root: str = ROOT) -> Cell:
    """The cell of workload entry ``w`` (``name``, ``config``,
    ``traffic``, ``chips``) under BENCHMARK.json's ``spec``."""
    name = w["name"]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _load_json(os.path.join(root, conf["file"]))
    traffic = _load_json(
        os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)],
    )


# --------------------------------------------------------------- traffic
@dataclasses.dataclass
class SimCell:
    """One simulation cell of a call: a protocol on one stream."""

    engine: dict  # EngineConfig fields (protocol, lanes, budget)
    stream: int  # index of the call's stream it runs on
    protocol: str = ""
    slots: int = 0
    meta: object = None  # the program's PlanMeta (set-up planning)


@dataclasses.dataclass
class Call:
    seeds: list  # generator seed of each stream
    streams: list  # bench.reference.data.Workload per stream
    cells: list  # SimCell
    sig: tuple = ()
    start_ns: int = 0
    end_ns: int = 0
    results: list = None


def pool_seeds(seed: int, n_calls: int, n_streams: int) -> list[list[int]]:
    """Generator seeds of every stream of every pool call, from --seed."""
    import numpy as np

    ss = np.random.SeedSequence(abs(int(seed)))
    flat = ss.generate_state(n_calls * n_streams, dtype=np.uint32)
    return [[int(x) for x in flat[c * n_streams:(c + 1) * n_streams]]
            for c in range(n_calls)]


def stream_fields(cell: Cell, stream: dict, seed: int) -> dict:
    """The fields of one stream: the configuration's ``workload``, the
    mix, the stream's own and its generator seed (TypeError for a field
    given twice). ``workload.kind`` names the generator
    (``bench/gen/<kind>.py``)."""
    return dict(**cell.config["workload"], **cell.traffic["mix"], **stream,
                seed=seed)


def build_pool(cell: Cell, seed: int) -> list[Call]:
    from bench import gen

    t = cell.traffic
    cores = int(cell.config["cores"])
    cells = []
    for si in range(len(t["streams"])):
        for proto in t["protocols"]:
            lanes = proto.get("n_exec", 0) + proto.get("n_cc", 0)
            if lanes != cores:
                raise ValueError(f"{proto} uses {lanes} cores, the "
                                 f"configuration has {cores}")
            cells.append((dict(proto, **cell.budget), si))
    pool = []
    for seeds in pool_seeds(seed, int(t["pool_calls"]), len(t["streams"])):
        streams = [gen.make_workload(stream_fields(cell, st, s))
                   for st, s in zip(t["streams"], seeds)]
        pool.append(Call(
            seeds=seeds, streams=streams,
            cells=[SimCell(engine=e, stream=si, protocol=e["protocol"],
                           slots=e["n_exec"] * e.get("window", 1))
                   for e, si in cells]))
    return pool


def program_workload(w, seed: int, cell: Cell, stream: dict):
    """The program's Workload holding the generated arrays (fresh copies,
    so no call reuses the arrays of another)."""
    from repro.core.workloads import Workload, WorkloadConfig

    wc = WorkloadConfig(**stream_fields(cell, stream, seed))
    return Workload(
        cfg=wc, keys=w.keys.copy(), modes=w.modes.copy(),
        nkeys=w.nkeys.copy(), part=w.part.copy(),
        exec_ops=w.exec_ops.copy(), ollp=w.ollp.copy(),
        ollp_miss=w.ollp_miss.copy(), num_records=w.num_records)


def call_args(call: Call, cell: Cell, budget: dict | None = None):
    """(EngineConfig, Workload) list for ``sweep.run_cells``."""
    from repro.core.engine import EngineConfig

    wls = [program_workload(w, s, cell, st) for w, s, st in
           zip(call.streams, call.seeds, cell.traffic["streams"])]
    return [(EngineConfig(**dict(c.engine, **(budget or {}))),
             wls[c.stream]) for c in call.cells]


def plan_shapes(call: Call, cell: Cell) -> None:
    """Plan the call with the program (host only) to learn each cell's
    plan shapes (``engine.plan_meta``: what a compiled runner is built
    for). The call's signature is its cells' shapes: calls that share it
    share their runners. Should the sweep driver ever key its runners on
    more than this, set-up warms too few shapes and the run fails on the
    compiles inside its window."""
    from repro.core import engine as engine_lib

    for c, (cfg, wl) in zip(call.cells, call_args(call, cell)):
        c.meta = engine_lib.plan_meta(cfg, engine_lib.make_plan(cfg, wl))
    call.sig = tuple(c.meta for c in call.cells)


# ------------------------------------------------------------ the device
class CompileCounter:
    """Counts compile events (tracing, lowering, backend compiles) and
    persistent-cache hits from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.compiles += 1


def check_device(chips: int):
    import jax

    from repro.kernels import resolve_interpret

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} device_kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {d.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, "
                         f"JAX found {len(devs)}")
    if resolve_interpret():
        raise SystemExit("REPRO_PALLAS_INTERPRET forces the Pallas "
                         "interpreter; unset it")
    return devs[:chips]


# ------------------------------------------------------------ correctness
def fingerprint(res) -> dict:
    """The counters of a program result that the reference reports."""
    return dict(
        commits=res.commits,
        aborts_deadlock=res.aborts_deadlock,
        aborts_ollp=res.aborts_ollp,
        wasted_ops=res.wasted_ops,
        rounds=res.rounds,
        rounds_total=res.raw["rounds_total"],
        total_commits=res.raw["total_commits"],
        next_txn=res.raw["next_txn"],
        steps_executed=res.raw["steps_executed"],
        breakdown=dict(res.breakdown),
        lat_hist=[int(x) for x in res.metrics.lat_hist],
    )


def mismatches(got: dict, want: dict) -> list[str]:
    """Names of the compared values that differ (one per counter, per
    breakdown category, per histogram bucket)."""
    bad = []
    for k, w in want.items():
        g = got.get(k)
        if isinstance(w, dict):
            bad += [f"{k}.{c}" for c in w if (g or {}).get(c) != w[c]]
        elif isinstance(w, list):
            g = list(g or [])
            bad += [f"{k}[{i}]" for i in range(max(len(w), len(g)))
                    if i >= len(g) or i >= len(w) or g[i] != w[i]]
        elif g != w:
            bad.append(k)
    return bad


def sample(calls: list[Call], seed: int, k: int) -> list[tuple[int, int]]:
    """(call, cell) pairs to check, drawn from the seed: one call, and in
    it ``k`` of its cells, one of each stream first (a group sharded over
    chips puts its streams' cells on different chips), then the rest."""
    import numpy as np

    rng = np.random.default_rng([abs(int(seed)), 12])
    ci = int(rng.integers(len(calls)))
    cells = calls[ci].cells
    streams = sorted({c.stream for c in cells})
    picked = [int(rng.choice([i for i, c in enumerate(cells)
                              if c.stream == s]))
              for s in rng.permutation(streams)[:k]]
    rest = [i for i in range(len(cells)) if i not in picked]
    more = min(k - len(picked), len(rest))
    if more > 0:
        picked += [int(i) for i in rng.choice(rest, size=more, replace=False)]
    return [(ci, i) for i in sorted(picked)]


def check(calls: list[Call], cell: Cell, seed: int) -> dict:
    from bench.reference import core as ref

    bad_total, checked = 0, []
    for ci, i in sample(calls, seed, CHECK_CELLS * cell.chips):
        call, c = calls[ci], calls[ci].cells[i]
        t0 = time.perf_counter()
        want = ref.simulate(ref.RefConfig(**c.engine), call.streams[c.stream])
        got = fingerprint(call.results[i])
        bad = mismatches(got, want)
        bad_total += len(bad)
        checked.append(dict(call=ci, cell=i, stream=c.stream,
                            protocol=c.protocol,
                            commits=got["commits"], mismatched=bad[:8],
                            reference_s=time.perf_counter() - t0))
        log(f"check call {ci} cell {i} ({c.protocol}): commits "
            f"{got['commits']} ref {want['commits']}, {len(bad)} "
            f"mismatched {bad[:8]}")
    return dict(mismatches=bad_total, checked=checked)


# ---------------------------------------------------------------- tracing
class TraceContext:
    """What a per-layer metric reader sees of the traced run: the trace,
    and the calls that completed inside its window (``calls``), each with
    its span on the trace's clock. ``devices`` are the chips with a plane
    in the trace, ``chips`` the number the cell holds."""

    def __init__(self, tr, cell: Cell, calls: list[Call], devices,
                 peaks: dict):
        self.trace = tr
        self.cell = cell
        self.calls = calls
        self.cells = sum(len(c.cells) for c in calls)
        self.devices = tr.device_names()[:len(devices)]
        self.chips = len(devices)
        self.peaks = peaks

    def note(self, what, value) -> None:
        """A reading beside the metric, for stderr."""
        log(f"trace: {what}: {value}")

    def in_calls(self, events):
        """The events that started inside a completed call's span."""
        spans = [(c.start_ns, c.end_ns) for c in self.calls]
        return [e for e in events
                if any(s <= e.start < t for s, t in spans)]

    def loop_iterations(self) -> int:
        """Loop iterations the chip executed in the completed calls: per
        call, the most steps of any of its cells. Counted only where the
        whole call ran as one group on one chip (``group_cells`` as
        ``run_cells`` reports it); 0 where a call did not."""
        if len(self.devices) != 1:
            return 0
        total = 0
        for call in self.calls:
            if any(r.raw["group_cells"] != len(call.cells)
                   for r in call.results):
                return 0
            total += max(r.raw["steps_executed"] for r in call.results)
        return total

    def kernel_roofline(self, kernel: str, call_bytes) -> float | None:
        """Share (%) of the peak-bandwidth bound in the kernel's device
        time. A kernel event is an op whose own name (``trace.op_name``)
        is the kernel's, or the kernel's with a ``.<n>`` suffix: ops that
        take its output as an operand do not count. Each is charged the
        bytes of the call's one cell; calls of several cells are left
        out (their lanes may share a kernel call)."""
        from bench import trace

        bw = self.peaks["hbm_bytes_per_s"]
        need_s = run_ns = 0.0
        for call in self.calls:
            if len(call.cells) != 1:
                continue
            nbytes = call_bytes(call.cells[0])
            if nbytes is None:
                continue
            for d in self.devices:
                for e in self.trace.ops(d):
                    name = trace.op_name(e.name)
                    if (name == kernel or name.startswith(kernel + ".")) and (
                            call.start_ns <= e.start < call.end_ns):
                        need_s += nbytes / bw
                        run_ns += e.dur
        if run_ns == 0:
            return None
        return 100.0 * need_s / (run_ns / 1e9)


def read_trace(tr, cell: Cell, calls: list[Call], devices, kind: str):
    """Per-layer metrics, device busy time and breakdown of a trace."""
    from bench import trace

    t0 = time.perf_counter()
    spans = sorted((e for evs in tr.host.values() for e in evs
                    if e.name == "bench.call"), key=lambda e: e.start)
    complete = []
    for call, e in zip(calls, spans):
        call.start_ns, call.end_ns = e.start, e.end
        if e.end <= tr.hi:
            complete.append(call)
    ctx = TraceContext(tr, cell, complete, devices, peaks_of(kind))
    metrics = {}
    for m in cell.per_layer:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    span = tr.hi - tr.lo
    busy = [trace.busy_ns(tr, d) for d in ctx.devices]
    ops: dict = {}
    for d in ctx.devices:
        for k, v in trace.sum_by_name(
                trace.in_window(tr, tr.ops(d)), short=True).items():
            ops[k] = ops.get(k, 0) + v
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    # a chip with no plane in the trace ran no operation: busy 0
    n_dev = ctx.chips
    breakdown = {
        "device_ops": [[k, v / 1e9 / n_dev] for k, v in top],
        "idle_gaps": trace.idle_gaps(tr, ctx.devices[0]) if ctx.devices
        else [],
    }
    log(f"trace: {sum(len(v) for d in tr.devices.values() for v in d.values())}"
        f" device events over {span / 1e9}s, {len(complete)} calls complete"
        f" in it, read in {time.perf_counter() - t0}s")
    device = dict(busy_s=sum(busy) / 1e9 / n_dev, window_s=span / 1e9)
    return metrics, device, breakdown


def peaks_of(kind: str) -> dict:
    table = _load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"device_kind {kind!r} has no peaks in "
                         "bench/peaks.json")
    return table[kind]


# -------------------------------------------------------------------- run
def run(cell: Cell, seed: int, seconds: float, traced: bool, devices,
        counter: CompileCounter) -> dict:
    import jax

    from repro.core import sweep

    mode = sweep.SweepMode(devices=cell.chips, **SWEEP_MODE)
    t_setup = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.pool"):
        pool = build_pool(cell, seed)
    shapes = {}
    for call in pool:
        plan_shapes(call, cell)
        shapes.setdefault(call.sig, call)
    for call in shapes.values():
        sweep.run_cells(call_args(call, cell, WARM_BUDGET), mode=mode)
    setup_s = time.perf_counter() - t_setup
    log(f"set-up: {len(pool)} calls in the pool, {len(shapes)} call "
        f"shapes warmed, {setup_s}s, compiles so far {counter.compiles} "
        f"(cache hits {counter.cache_hits})")

    recorder = None
    if traced:
        from bench import trace

        seconds = min(seconds, TRACE_SECONDS)
        recorder = trace.Recorder(TRACE_CAP_SECONDS)
    before = counter.compiles
    done: list[Call] = []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            src = pool[len(done) % len(pool)]
            call = dataclasses.replace(src, cells=[
                dataclasses.replace(c) for c in src.cells])
            args = call_args(call, cell)
            with jax.profiler.TraceAnnotation("bench.call"):
                call.results = sweep.run_cells(args, mode=mode)
            done.append(call)
            if time.perf_counter() - t0 >= seconds or (
                    recorder is not None and recorder.stopped):
                break
    window_s = time.perf_counter() - t0
    window_compiles = counter.compiles - before
    n_cells = sum(len(c.cells) for c in done)
    cycled = max(0, len(done) - len(pool))
    log(f"window: {len(done)} calls, {n_cells} cells in {window_s}s; "
        f"compiles inside the window: {window_compiles}; "
        f"calls past the pool (cycled): {cycled}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    result = {"correct": False, "attempted": n_cells, "failed": 0}
    if traced:
        metrics, dev_extra, breakdown = read_trace(
            recorder.finish(), cell, done, devices, devices[0].device_kind)
    else:
        metrics = {"cell_s": {"value": window_s / n_cells, "unit": "s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}
        dev_extra, breakdown = {}, None
    # run_cells freed the program's device state when it returned; drop
    # its compiled programs as well before the reference runs
    jax.clear_caches()
    verdict = check(done, cell, seed)
    result["failed"] = sum(1 for c in verdict["checked"] if c["mismatched"])
    verdict["window_compiles"] = window_compiles
    result["correct"] = all(verdict[k] <= v for k, v in LIMITS.items())
    d = devices[0]
    result["metrics"] = metrics
    result["device"] = dict(platform=d.platform, kind=d.device_kind,
                            count=len(devices), memory_peak_bytes=peak,
                            **dev_extra)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window_compiles"] = window_compiles
    result["checked"] = verdict["checked"]
    result["checks"] = {k: {"value": verdict[k], "limit": v}
                        for k, v in LIMITS.items()}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    cell = load_cell(args.workload)
    # the persistent compile cache lives at a fixed path in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    counter = CompileCounter()
    devices = check_device(cell.chips)
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 counter)
    for k, v in result["checks"].items():
        log(f"check {k}={v['value']} limit={v['limit']}")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
