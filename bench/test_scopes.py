"""Self-checks of the readers of the program's scopes and spans
(``bench/scopes.py`` and the ``stage_*``, ``plan_span_s`` and ``idle_*``
metrics), on a trace and an HLO text written by hand, and the runner text
of a real call on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q -p no:cacheprovider \
        bench/test_scopes.py
"""

from __future__ import annotations

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import pytest  # noqa: E402

from bench import run, scopes, trace  # noqa: E402

BODY = "jit(run_chunk)/while/body/"
# a runner's compiled text: a fusion whose root (a bitcast) has no
# op_name of its own, a fusion across two scopes, a kernel call, a carry
# copy and an op outside every stage, in a while loop's body
HLO = f"""HloModule jit_run_chunk, is_scheduled=true

%fused_computation (param_0: s32[8]) -> s32[8,1] {{
  %param_0 = s32[8]{{0}} parameter(0)
  %add.1 = s32[8]{{0}} add(%param_0, %param_0), metadata={{op_name="{BODY}stage.admit/add"}}
  %mul.2 = s32[8]{{0}} multiply(%add.1, %add.1), metadata={{op_name="{BODY}stage.grant/mul"}}
  ROOT %bitcast.3 = s32[8,1]{{1,0}} bitcast(%mul.2)
}}

%fused_computation.1 (param_0.1: s32[8]) -> s32[8] {{
  %param_0.1 = s32[8]{{0}} parameter(0)
  %sub.4 = s32[8]{{0}} subtract(%param_0.1, %param_0.1), metadata={{op_name="{BODY}stage.grant/sub"}}
  ROOT %max.5 = s32[8]{{0}} maximum(%sub.4, %param_0.1), metadata={{op_name="{BODY}stage.leap/max"}}
}}

%body (p: (s32[8], s32[])) -> (s32[8], s32[]) {{
  %p = (s32[8]{{0}}, s32[]) parameter(0)
  %gte.0 = s32[8]{{0}} get-tuple-element(%p), index=0
  %fusion.1 = s32[8,1]{{1,0}} fusion(%gte.0), kind=kLoop, calls=%fused_computation
  %fusion.2 = s32[8]{{0}} fusion(%gte.0), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{BODY}stage.leap/max"}}
  %lock_grant.3 = s32[8]{{0}} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}stage.grant/kernel.lock_grant/pallas_call"}}
  %copy.6 = s32[8]{{0}} copy(%lock_grant.3)
  %select.9 = s32[8]{{0}} select(%gte.0, %copy.6, %copy.6), metadata={{op_name="{BODY}select_n"}}
  %gte.1 = s32[] get-tuple-element(%p), index=1
  ROOT %tuple.7 = (s32[8]{{0}}, s32[]) tuple(%select.9, %gte.1)
}}

ENTRY %main.8 (x: s32[8]) -> (s32[8], s32[]) {{
  %x = s32[8]{{0}} parameter(0)
  %c = s32[] constant(0)
  %tuple.9 = (s32[8]{{0}}, s32[]) tuple(%x, %c)
  ROOT %while.7 = (s32[8]{{0}}, s32[]) while(%tuple.9), condition=%cond, body=%body, metadata={{op_name="jit(run_chunk)/while"}}
}}
"""
TPU0 = "/device:TPU:0"


def _ev(name, start, dur):
    return trace.Event(name, start, dur)


def hand_trace() -> trace.Trace:
    """One chip and one call [50, 1000): a runner execution [100, 600)
    with two loop iterations, another module's execution [650, 700), and
    the program's host spans, nested in ``repro.run_cells``."""
    dev = {
        "XLA Modules": [_ev("jit_run_chunk(1)", 100, 500),
                        _ev("jit_copy(2)", 650, 50)],
        "XLA Ops": [
            _ev("%while.7 = (s32[8], s32[]) while(%tuple.9)", 100, 500),
            _ev("%fusion.1 = s32[8,1] fusion(s32[8] %gte.0), kind=kLoop",
                110, 40),
            _ev("fusion.2", 150, 80),
            _ev("lock_grant.3", 230, 100),
            _ev("copy.6", 330, 30),
            _ev("select.9", 360, 20),
            _ev("call.3", 380, 20),  # a container: not counted
            _ev("fusion.1", 400, 40),
            _ev("unknown.99", 440, 10),  # in no instruction of the text
            _ev("fusion.1", 650, 50),  # another module's op
        ],
    }
    host = {"python3": [
        _ev("bench.call", 50, 950),
        _ev("repro.run_cells", 60, 930),
        _ev("repro.plan", 60, 30),
        _ev("repro.upload", 90, 10),
        _ev("repro.dispatch", 100, 20),
        _ev("repro.sync", 120, 520),
        _ev("repro.dispatch", 640, 20),
        _ev("repro.sync", 660, 60),
        _ev("repro.finish", 900, 80),
        _ev("repro.plan", 1010, 40),  # outside every completed call
    ]}
    return trace.Trace(devices={TPU0: dev}, host=host, lo=0, hi=1100)


def hand_ctx(tr=None) -> run.TraceContext:
    cell = run.load_cell("tpcc_w4_80c.orthrus")
    c = run.SimCell(engine={}, stream=0, protocol="orthrus", slots=256)
    res = type("R", (), {"raw": {"steps_executed": 2, "group_cells": 1}})()
    call = run.Call(seeds=[0], streams=[None], cells=[c], sig=("s",),
                    start_ns=50, end_ns=1000, results=[res])
    return run.TraceContext(tr or hand_trace(), cell, [call], [0],
                            run.peaks_of("TPU v5 lite"))


def _read(name, ctx):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


def test_hlo_op_names_by_hand():
    names = scopes.hlo_op_names(HLO)
    # the root bitcast has no op_name: its operand's scope counts
    assert scopes.stage_of(names["fusion.1"]) == "grant"
    # a fusion across scopes takes its root's
    assert scopes.stage_of(names["fusion.2"]) == "leap"
    assert scopes.stage_of(names["lock_grant.3"]) == "grant"
    assert names["copy.6"] == ""
    assert scopes.stage_of(names["select.9"]) == ""
    assert names["while.7"] == "jit(run_chunk)/while"
    assert scopes.stage_of(names["add.1"]) == "admit"
    assert scopes.stage_of("a/stage.grant/b/stage.x/c") == "x"


def test_stage_readers_by_hand(monkeypatch):
    monkeypatch.setattr(scopes, "runner_hlo", lambda cell, call: HLO)
    ctx = hand_ctx()
    # leaf ops of the runner module only: fusion.1 twice and the kernel
    # in grant, fusion.2 in leap, the copy and the select in no stage;
    # the while, the call and the other module's op are left out; the
    # unknown op counts nowhere. Two loop iterations.
    assert scopes.stage_ns(ctx) == {"grant": 180, "leap": 80, "admit": 0,
                                    "": 50}
    assert _read("stage_grant_us", ctx) == pytest.approx(0.09)
    assert _read("stage_leap_us", ctx) == pytest.approx(0.04)
    assert _read("stage_loop_us", ctx) == pytest.approx(0.025)
    # named by the runner, but no op is rooted in it
    assert _read("stage_admit_us", ctx) == 0.0
    # not named by the runner: left out of the line
    assert _read("stage_commit_us", ctx) is None
    # the stages and the rest add up to no more than step_us
    staged = sum(_read(f"stage_{s}_us", ctx) for s in ("grant", "leap",
                                                       "admit", "loop"))
    assert staged <= _read("step_us", ctx)


def test_stage_readers_without_scopes(monkeypatch):
    """A runner that names no stage (a program without the scopes)."""
    monkeypatch.setattr(scopes, "runner_hlo",
                        lambda cell, call: HLO.replace("stage.", "s_"))
    ctx = hand_ctx()
    for name in ("stage_grant_us", "stage_loop_us", "stage_admit_us"):
        assert _read(name, ctx) is None


def test_idle_and_plan_readers_by_hand():
    ctx = hand_ctx()
    # idle in the call: [50, 100), [600, 650), [700, 1000); each instant
    # charged to the innermost span: plan 30, upload 10, chunk 40 + 10 +
    # 20 + 80, and the rest (bench.call or run_cells alone) to no metric
    assert scopes.idle_by_span(ctx) == {"plan": 30, "upload": 10,
                                        "chunk": 150, "other": 210}
    assert _read("idle_plan_s", ctx) == pytest.approx(30e-9)
    assert _read("idle_upload_s", ctx) == pytest.approx(10e-9)
    assert _read("idle_chunk_s", ctx) == pytest.approx(150e-9)
    # the one repro.plan span inside the completed call
    assert _read("plan_span_s", ctx) == pytest.approx(30e-9)


def test_host_readers_without_spans():
    tr = hand_trace()
    tr.host = {"python3": [e for e in tr.host["python3"]
                           if not e.name.startswith("repro.")]}
    ctx = hand_ctx(tr)
    for name in ("idle_plan_s", "idle_upload_s", "idle_chunk_s",
                 "plan_span_s"):
        assert _read(name, ctx) is None


def test_innermost_and_idle_intervals():
    evs = [_ev("a", 0, 100), _ev("b", 10, 20), _ev("c", 10, 5),
           _ev("d", 200, 10)]
    assert scopes.innermost(evs) == [(0, 10, "a"), (10, 15, "c"),
                                     (15, 30, "b"), (30, 100, "a"),
                                     (200, 210, "d")]
    busy = scopes.busy_union([_ev("x", 5, 10), _ev("y", 8, 10),
                              _ev("z", 40, 5)])
    assert busy == [[5, 18], [40, 45]]
    assert scopes.idle_in(busy, 0, 50) == [(0, 5), (18, 40), (45, 50)]
    assert scopes.idle_in(busy, 10, 42) == [(18, 40)]


def test_runner_hlo_names_the_batch_stages():
    """The runner text of a real call (CPU, small): every stage of the
    batch step is named, and a stage of the lock step is not."""
    cell = run.load_cell("ycsb10m_40c.dgcc_hot64")
    cell.config["workload"]["num_txns"] = 256
    cell.traffic["pool_calls"] = 1
    call = run.build_pool(cell, 2**31 + 5)[0]
    run.plan_shapes(call, cell)
    names = scopes.hlo_op_names(scopes.runner_hlo(cell, call))
    stages = {scopes.stage_of(op) for op in names.values()} - {""}
    assert stages == {"rollover", "admit", "msg", "wavefront", "schedule",
                      "commit", "accounting", "leap"}
