"""The program's measurement points: the round steps' stage scopes and the
sweep driver's host spans.

* every protocol's compiled chunk runner names, in its HLO ``op_name``
  metadata, each ``stage.<name>`` scope its step builder runs (and the
  ``kernel.<name>`` scope of its kernel call site);
* one ``run_cells`` call under an in-memory profiler session records the
  six ``repro.*`` host spans, nested in ``repro.run_cells``: one
  ``repro.plan`` per cell, one ``repro.upload`` per cell and per group,
  one ``repro.dispatch`` and one ``repro.sync`` per chunk boundary, one
  ``repro.finish`` per group.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine, sweep
from repro.core.engine import EngineConfig
from repro.core.workloads import WorkloadConfig, make_workload

YCSB = dict(kind="ycsb", num_hot=16)
TPCC = dict(kind="tpcc", num_warehouses=4)
BUDGET = dict(max_rounds=64, warmup_rounds=16, chunk_rounds=16)

LOCK = {"admit", "acquire", "request", "grant", "transition", "accounting",
        "leap"}
SHARED = LOCK | {"release", "line_cost"}  # lock tables the lanes share
BATCH = {"rollover", "admit", "msg", "wavefront", "schedule", "commit",
         "accounting", "leap"}
# protocol -> (workload, engine kwargs, stage scopes, kernel scopes)
CASES = {
    "twopl_waitdie": (YCSB, dict(n_exec=4), SHARED | {"deadlock"}, set()),
    "twopl_waitfor": (YCSB, dict(n_exec=4), SHARED | {"deadlock"}, set()),
    "twopl_dreadlocks": (YCSB, dict(n_exec=4), SHARED | {"deadlock"},
                         set()),
    "deadlock_free": (YCSB, dict(n_exec=4), SHARED, set()),
    "partitioned_store": (YCSB, dict(n_exec=4), SHARED, set()),
    "orthrus": (TPCC, dict(n_cc=2, n_exec=4, window=2), LOCK | {"cc"},
                {"lock_grant"}),
    "dgcc": (YCSB, dict(n_cc=2, n_exec=4, window=2), BATCH,
             {"dep_wavefront"}),
    "quecc": (YCSB, dict(n_cc=2, n_exec=4, window=2), BATCH,
              {"dep_wavefront"}),
    "scheduled": (YCSB, dict(n_exec=4), BATCH, {"dep_wavefront"}),
}
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _cell(protocol, num_txns=128, seed=3):
    wkw, ekw, _, _ = CASES[protocol]
    cfg = EngineConfig(protocol=protocol, **ekw, **BUDGET)
    return cfg, make_workload(WorkloadConfig(**wkw, num_txns=num_txns,
                                             seed=seed))


@pytest.mark.parametrize("protocol", sorted(CASES))
def test_runner_names_every_stage_scope(protocol):
    cfg, wl = _cell(protocol)
    g = sweep._GroupRun([cfg], [engine.make_plan(cfg, wl)], sweep.SERIAL_MODE)
    runner = sweep.get_runner(cfg, g.meta, batched=g.batched, mesh=g.mesh)
    text = runner.lower(g.p, g.state, jnp.asarray(16, jnp.int32)).compile(
    ).as_text()
    named = " ".join(OP_NAME.findall(text))
    _, _, stages, kernels = CASES[protocol]
    assert set(re.findall(r"\bstage\.(\w+)", named)) == stages
    assert set(re.findall(r"\bkernel\.(\w+)", named)) == kernels


def _spans(xspace: bytes) -> list:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


@pytest.mark.parametrize("protocol", ["twopl_waitdie", "orthrus", "dgcc"])
def test_run_cells_records_the_host_spans(protocol):
    from jax._src.lib import _profiler

    # two cells of different plan shapes: two groups
    cells = [_cell(protocol), _cell(protocol, num_txns=192, seed=4)]
    mode = sweep.SweepMode(devices=1, pipeline=1, early_exit=False)
    sweep.run_cells(cells, mode=mode)  # compile outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    session = _profiler.ProfilerSession(opts)
    try:
        sweep.run_cells(cells, mode=mode)
    finally:
        spans = _spans(session.stop())
    names = [s[0] for s in spans]
    (_, lo, hi, args), = [s for s in spans if s[0] == "repro.run_cells"]
    assert args == {"cells": 2, "groups": 2}
    assert all(lo <= s <= e <= hi for _, s, e, _ in spans)
    rounds = list(sweep.chunk_boundaries(cells[0][0]))
    assert [a["protocol"] for n, _, _, a in spans
            if n == "repro.plan"] == [protocol] * 2
    assert names.count("repro.upload") == 2 + 2
    assert sorted(a["round"] for n, _, _, a in spans
                  if n == "repro.dispatch") == sorted(rounds * 2)
    assert names.count("repro.sync") == 2 * len(rounds)
    assert names.count("repro.finish") == 2
