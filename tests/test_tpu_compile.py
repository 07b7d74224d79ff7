"""Compile-only checks for a described TPU v5e (no chip needed).

The interpret-mode kernel tests cannot see what the chip's compiler
refuses (``cumsum``, single-element extraction, slices off the
``(8, 128)`` tiling). These tests lower the ``lock_grant`` and
``dep_wavefront`` kernels, and the ``twopl_waitdie``, ``twopl_dreadlocks``,
``dgcc`` and ``orthrus`` chunk runners, for one chip of a described
``v5e:2x2`` topology and compile them with the installed TPU compiler,
and check the compiled lock-table loop for per-record copies and row
scatters, and ORTHRUS's grant pass for gathers and sorts by scope.
Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under pytest-xdist
every worker imports this file. The persistent compile cache is off
around the compiles, since an entry written for a described chip cannot
be read back without one.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine as engine_lib
from repro.core import sweep
from repro.core.engine import EngineConfig
from repro.core.workloads import WorkloadConfig, make_workload
from repro.kernels.dep_wavefront.ops import dep_wavefront_ready
from repro.kernels.lock_grant.ops import lock_grant


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        with pytest.MonkeyPatch.context() as mp:
            # the TPU library reads these once, as it loads: without
            # them it writes its logs to files under /tmp
            mp.setenv("TPU_LOG_DIR", "disabled")
            mp.setenv("LIBTPU_INIT_ARGS", "--logtostderr")
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
    except Exception as e:  # no libtpu, or it cannot describe a v5e
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(x, sharding, lead=()):
    x = np.asarray(x) if not hasattr(x, "dtype") else x
    return jax.ShapeDtypeStruct(lead + np.shape(x), x.dtype,
                                sharding=sharding)


def _kernel_count(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("n", [1024, 4096])
def test_lock_grant_compiles(n, one_chip, no_persistent_cache):
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    num_records = 512
    rec = jax.ShapeDtypeStruct((num_records,), jnp.int32, sharding=one_chip)
    # 1024-entry blocks: one grid step at 1024, four (with the SMEM
    # carry between them) at 4096
    op = functools.partial(lock_grant, num_records=num_records,
                           block_n=1024, interpret=False)
    compiled = jax.jit(op).lower(i32, i32, i32, rec, rec).compile()
    assert _kernel_count(compiled) == 1


@pytest.mark.parametrize("n", [1024, 4096])
def test_dep_wavefront_compiles(n, one_chip, no_persistent_cache):
    num_txns = 256
    edge = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    done = jax.ShapeDtypeStruct((num_txns,), jnp.bool_, sharding=one_chip)
    op = functools.partial(dep_wavefront_ready, num_txns=num_txns,
                           block_n=1024, interpret=False)
    compiled = jax.jit(op).lower(edge, edge, done).compile()
    assert _kernel_count(compiled) == 1


# A YCSB size no other test uses, so the runner is traced here, with the
# compiled kernel, and never taken from an earlier interpreted trace.
_YCSB = dict(kind="ycsb", num_txns=3072, num_records=300_000, num_hot=64,
             seed=0)
_SIM = dict(max_rounds=6000, warmup_rounds=2000, chunk_rounds=2000,
            target_commits=100_000_000)


def _compile_runner(eng, one_chip, cells=0, wl=_YCSB):
    """Compile the chunk runner of ``eng`` on the stream ``wl`` (``_YCSB``
    unless given) for one described chip: one cell, or a vmapped group of
    ``cells``. Returns the compiled runner and the plan's shapes."""
    cfg = EngineConfig(**eng, **_SIM)
    plan = engine_lib.make_plan(cfg, make_workload(WorkloadConfig(**wl)))
    meta = engine_lib.plan_meta(cfg, plan)
    p = engine_lib.plan_device(cfg, plan)
    if cfg.is_batch_planned:
        state = engine_lib._batch_state0(cfg, plan, cfg.n_slots)
    else:
        state = engine_lib._state0(cfg, plan.num_records, cfg.n_slots,
                                   meta.max_keys)
    lead = (cells,) if cells else ()
    runner = sweep.get_runner(cfg, meta, batched=bool(cells))
    spec = functools.partial(_spec, sharding=one_chip, lead=lead)
    compiled = runner.lower(
        jax.tree.map(spec, p), jax.tree.map(spec, state),
        jax.ShapeDtypeStruct(lead, jnp.int32, sharding=one_chip),
    ).compile()
    return compiled, meta


@pytest.mark.parametrize("eng,kernels", [
    (dict(protocol="twopl_waitdie", n_exec=40), 0),
    (dict(protocol="dgcc", n_cc=8, n_exec=32, window=4,
          kernel_impl="pallas"), 1),
])
def test_chunk_runner_compiles(eng, kernels, one_chip, no_persistent_cache,
                               monkeypatch):
    # this process is on the CPU, where the kernel op would resolve to
    # the interpreter; the described chip needs the compiled kernel
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    compiled, _ = _compile_runner(eng, one_chip)
    assert _kernel_count(compiled) == kernels


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
# an instruction's shape (the first of a tuple's), opcode and op_name
_OP = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = \(?\w+\[([\d,]*)\]\S*(?:, \S+)*"
                 r" ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition)=%([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}"
)


def _ops(text: str, loop_only: bool) -> list[tuple[str, tuple[int, ...], str]]:
    """``(opcode, dims, op_name)`` of the array- and tuple-shaped
    instructions of a compiled module's text (``dims`` of a tuple's first
    element): all of them, or only those a ``while`` body runs (the body
    and every computation it calls, fusions included)."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    names = set(comps)
    if loop_only:
        names, todo = set(), re.findall(r"body=%([\w.\-]+)", text)
        while todo:
            c = todo.pop()
            if c in names or c not in comps:
                continue
            names.add(c)
            for line in comps[c]:
                for one, many in _CALLED.findall(line):
                    todo += [one] if one else re.findall(r"%([\w.\-]+)",
                                                         many)
    ops = []
    for c in names:
        for line in comps[c]:
            m = _OP.match(line)
            if m:
                dims = tuple(int(d) for d in m.group(1).split(",") if d)
                name = _OP_NAME.search(line)
                ops.append((m.group(2), dims, name.group(1) if name else ""))
    return ops


@pytest.mark.parametrize("eng,cells", [
    pytest.param(dict(protocol="twopl_waitdie", n_exec=40), 0,
                 id="waitdie"),
    pytest.param(dict(protocol="twopl_waitdie", n_exec=40), 2,
                 id="waitdie-vmapped"),
    # 80 slots: three reader-bitmask words
    pytest.param(dict(protocol="twopl_dreadlocks", n_exec=80), 0,
                 id="dreadlocks-80"),
])
def test_record_columns_not_copied(eng, cells, one_chip, no_persistent_cache):
    """The lock-table step's per-record state is 1-D [R] columns (a
    vmapped group adds a leading cell axis): the round loop copies or
    scatters into no [R, F] array, and a single cell's runner copies no
    per-record array. A row scatter into an [R, F] array costs several
    times a 1-D one on the chip, and an [R, F] array read in another
    layout than it is written in is copied whole every round."""
    compiled, meta = _compile_runner(eng, one_chip, cells)
    text = compiled.as_text()
    R = meta.num_records
    loop = _ops(text, loop_only=True)
    assert any(op == "scatter" and R in dims for op, dims, _ in loop)
    for op, dims, _ in loop:
        if op in ("copy", "scatter") and R in dims:
            assert dims.index(R) == len(dims) - 1, (op, dims)
    if cells:
        # the vmapped loop's per-lane select copies [cells, R] columns
        # each round; on a TPU v5e that measured faster than a loop
        # written out to skip the select for them
        return
    copied = [dims for op, dims, _ in _ops(text, loop_only=False)
              if op == "copy" and R in dims]
    assert not copied, copied


# A TPC-C stream no other test compiles, on 50 stock records a warehouse
_TPCC = dict(kind="tpcc", num_txns=512, num_warehouses=4, stock_per_wh=50,
             seed=0)


def test_orthrus_grant_pass_gathers(one_chip, no_persistent_cache,
                                    monkeypatch):
    """ORTHRUS's grant pass sorts its T * K entries once, carrying their
    kind and index, reads the lock table at the sorted keys and puts the
    grants back in slot order once. So the round loop's ``stage.grant``
    gathers T * K entries at most twice, for the two lock-table columns,
    and sorts at most twice. On the chip a gather of a few thousand
    scalars costs several microseconds; moving the entries into sorted
    order and back by gathers took five more of them a round."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    eng = dict(protocol="orthrus", n_cc=4, n_exec=16, window=4,
               kernel_impl="pallas")
    compiled, meta = _compile_runner(eng, one_chip, wl=_TPCC)
    assert _kernel_count(compiled) == 1
    n = 16 * 4 * meta.max_keys
    grant = [(op, dims) for op, dims, name in _ops(compiled.as_text(),
                                                   loop_only=True)
             if "/stage.grant/" in name]
    gathers = [dims for op, dims in grant
               if op == "gather" and dims and dims[0] >= n]
    sorts = [dims for op, dims in grant if op == "sort"]
    assert 0 < len(gathers) <= 2, gathers
    assert 0 < len(sorts) <= 2, sorts
