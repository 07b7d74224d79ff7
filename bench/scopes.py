"""The program's own scopes and spans in a traced run.

The round steps name their stages with ``jax.named_scope("stage.<name>")``
(and kernel call sites with ``kernel.<name>``), which XLA keeps as each
HLO instruction's ``op_name`` metadata; the sweep driver opens
``repro.*`` host spans (``jax.profiler.TraceAnnotation``) around
planning, upload, the chunk loop's dispatches and syncs, and result
assembly. Both land in the profiler's trace on one clock. This module
reduces them for the per-layer readers in ``bench/metrics/``:

* ``stage_ns``: device time of the chunk runner's leaf ops per stage.
  The TPU trace names a device op by its HLO instruction only (no
  ``op_name``), so the stage is looked up in the runner's compiled HLO
  text, once per distinct (call shape, op). A fusion takes the scope of
  its fused computation's root instruction; a root without an
  ``op_name`` of its own (a bitcast, a tuple) takes that of its nearest
  operand that has one. Ops outside every stage (the runner's loop,
  column unpack and repack, carry copies) count under ``""``;
* ``idle_by_span``: chip idle time inside completed calls, split by the
  innermost ``repro.*`` span open at each instant;
* ``spans``: the ``repro.*`` host spans of the completed calls.

A program without the scopes (or spans) gives None, and the readers leave
their metric out of the line. Plain data in, plain data out, apart from
``runner_hlo``, which compiles the call's runner; ``bench/test_scopes.py``
checks the rest on a trace and an HLO text written by hand.
"""

from __future__ import annotations

import bisect
import re

from bench import trace

MODULE = "jit_run_chunk"
# ops that only contain others (their body's ops are events of their own)
CONTAINER = re.compile(r"(while|conditional|call)(\.\d+)?")
STAGE = re.compile(r"(?:^|/)stage\.(\w+)")
SPAN_PREFIX = "repro."
# the metric each innermost host span's idle time goes to
IDLE_OF_SPAN = {"repro.plan": "plan", "repro.upload": "upload",
                "repro.dispatch": "chunk", "repro.sync": "chunk",
                "repro.finish": "chunk"}

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


# ------------------------------------------------------------ HLO text
def hlo_op_names(text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction of a compiled
    HLO module's text, with a fusion given its root's (see the module
    docstring); "" where nothing names one."""
    # computation -> {instr: (op_name, called computation, operands),
    # "<root>": its root instr}; instr -> the computation holding it
    comps: dict = {}
    instrs: dict = {}
    cur = None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            cur = {}
            name = line.split()[1] if line.startswith("ENTRY") else (
                line.split()[0])
            comps[name.lstrip("%")] = cur
            continue
        if cur is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        cur[name] = (op.group(1) if op else "",
                     calls.group(1) if calls else None,
                     _REF.findall(rest.split(", metadata=", 1)[0]))
        if line.lstrip().startswith("ROOT"):
            cur["<root>"] = name
        instrs[name] = cur

    def described(comp: dict, start: str) -> str:
        # the root, else its nearest operand, whose op_name is a scope path
        seen, todo = set(), [start]
        while todo:
            nxt = []
            for n in todo:
                if n in seen or n not in comp:
                    continue
                seen.add(n)
                op, _calls, refs = comp[n]
                if "/" in op:
                    return op
                nxt += refs
            todo = nxt
        return ""

    out = {}
    for name, comp in instrs.items():
        op, calls, _refs = comp[name]
        if calls in comps and "<root>" in comps[calls]:
            fused = comps[calls]
            op = described(fused, fused["<root>"]) or op
        out[name] = op
    return out


def stage_of(op_name: str) -> str:
    """The innermost ``stage.<name>`` of an op_name path; "" for none."""
    found = STAGE.findall(op_name)
    return found[-1] if found else ""


def runner_hlo(cell, call) -> str:
    """Compiled HLO text of the chunk runner that ran ``call`` as one
    group on one chip: the sweep driver's own group state and runner for
    the call's cells, lowered and compiled once more."""
    import jax
    import jax.numpy as jnp

    from bench import run
    from repro.core import engine, sweep

    args = run.call_args(call, cell)
    cfgs = [c for c, _ in args]
    plans = [engine.make_plan(c, w) for c, w in args]
    g = sweep._GroupRun(cfgs, plans, sweep.SweepMode(devices=1,
                                                     **run.SWEEP_MODE))
    runner = sweep.get_runner(cfgs[0], g.meta, batched=g.batched,
                              mesh=g.mesh)
    r_end = jax.ShapeDtypeStruct((g.nb,) if g.batched else (), jnp.int32)
    return runner.lower(g.p, g.state, r_end).compile().as_text()


# --------------------------------------------------------- device side
def runner_ops(tr, dev: str, calls) -> list:
    """[(call, short op name, ns)] of the leaf ops of ``dev`` that ran
    inside a ``jit_run_chunk`` module execution that started inside one
    of the calls' spans."""
    mods = sorted(((e.start, e.end, c) for c in calls
                   for e in tr.modules(dev) if e.name.startswith(MODULE)
                   and c.start_ns <= e.start < c.end_ns),
                  key=lambda m: m[0])
    starts = [m[0] for m in mods]
    leaf: dict = {}  # event name -> short op name, or None for a container
    out = []
    for e in tr.ops(dev):
        i = bisect.bisect_right(starts, e.start) - 1
        if i < 0 or e.start >= mods[i][1]:
            continue
        if e.name not in leaf:
            op = trace.op_name(e.name)
            leaf[e.name] = None if CONTAINER.fullmatch(op) else op
        if leaf[e.name] is not None:
            out.append((mods[i][2], leaf[e.name], e.dur))
    return out


def stage_ns(ctx) -> dict | None:
    """{stage: device ns summed over the cell's chips} of the runner's
    leaf ops in the completed calls ("" for ops outside every stage), or
    None where the runner names no stage. The stage is looked up in
    ``runner_hlo``'s text once per distinct (call shape, op), and the
    result is kept on the context. Notes how much of the ops' time no
    instruction of the text accounts for."""
    if "stage_ns" in vars(ctx):
        return ctx.stage_ns
    texts: dict = {}  # call shape -> {op: op_name}
    for call in ctx.calls:
        if call.sig not in texts:
            texts[call.sig] = hlo_op_names(runner_hlo(ctx.cell, call))
    named = {stage_of(op) for names in texts.values()
             for op in names.values()} - {""}
    result = None
    if named:
        staged: dict = {}  # (call shape, op) -> stage, None where unknown
        result = dict.fromkeys(named | {""}, 0)
        unknown = total = 0
        for d in ctx.devices:
            for call, op, dur in runner_ops(ctx.trace, d, ctx.calls):
                key = (call.sig, op)
                if key not in staged:
                    name = texts[call.sig].get(op)
                    staged[key] = None if name is None else stage_of(name)
                total += dur
                if staged[key] is None:
                    unknown += dur
                else:
                    result[staged[key]] += dur
        ctx.note("runner op time no HLO instruction names (%)",
                 100.0 * unknown / total if total else 0.0)
    ctx.stage_ns = result
    return result


def stage_us(ctx, stage: str) -> float | None:
    """Device time per loop iteration (us) of one stage ("" for the ops
    outside every stage), with iterations counted as ``step_us`` counts
    them; None where the runner does not name the stage or no iteration
    was counted."""
    iters = ctx.loop_iterations()
    if iters == 0:
        return None
    ns = stage_ns(ctx)
    if ns is None or stage not in ns:
        return None
    return ns[stage] / 1e3 / iters


# ----------------------------------------------------------- host side
def spans(ctx, name: str | None = None) -> list:
    """``repro.*`` host spans (or those called ``name``) that started
    inside a completed call's span."""
    return ctx.in_calls([
        e for evs in ctx.trace.host.values() for e in evs
        if e.name.startswith(SPAN_PREFIX) and (name is None
                                               or e.name == name)])


def innermost(evs) -> list:
    """[(start, end, name)]: the instants where some of the (nested) spans
    are open, cut into intervals each named by the innermost span open
    over it, in time order."""
    cuts = sorted({t for e in evs for t in (e.start, e.end)})
    out = []
    for s, t in zip(cuts, cuts[1:]):
        open_ = [e for e in evs if e.start <= s and t <= e.end]
        if open_:
            best = max(open_, key=lambda e: (e.start, -e.dur))
            out.append((s, t, best.name))
    return out


def busy_union(events) -> list:
    """The union of the events' intervals, as sorted disjoint (s, t)."""
    out: list = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return out


def idle_in(busy: list, lo: int, hi: int) -> list:
    """Idle (s, t) intervals of [lo, hi) outside ``busy_union``'s."""
    i = max(bisect.bisect_right([b[0] for b in busy], lo) - 1, 0)
    out, cur = [], lo
    while i < len(busy) and busy[i][0] < hi:
        s, t = busy[i]
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
        i += 1
    if cur < hi:
        out.append((cur, hi))
    return out


def idle_by_span(ctx) -> dict | None:
    """{"plan" | "upload" | "chunk" | "other": ns} of chip idle time
    inside the completed calls' spans, summed over the cell's chips,
    each instant charged to the innermost ``repro.*`` span open then
    (``IDLE_OF_SPAN``; "other" where none of those is); None where the
    calls hold no ``repro.*`` span. Kept on the context."""
    if "idle_ns" in vars(ctx):
        return ctx.idle_ns
    evs = spans(ctx)
    if not evs or not ctx.devices:
        ctx.idle_ns = None
        return None
    segs = innermost(evs)
    ends = [t for _, t, _ in segs]
    out = dict.fromkeys(("plan", "upload", "chunk", "other"), 0)
    for d in ctx.devices:
        busy = busy_union(ctx.trace.ops(d))
        for call in ctx.calls:
            for s, t in idle_in(busy, call.start_ns, call.end_ns):
                i, charged = bisect.bisect_right(ends, s), 0
                while i < len(segs) and segs[i][0] < t:
                    a, b, name = segs[i]
                    ov = min(b, t) - max(a, s)
                    out[IDLE_OF_SPAN.get(name, "other")] += ov
                    charged += ov
                    i += 1
                out["other"] += (t - s) - charged
    total = sum(out.values())
    ctx.note("idle in calls charged to plan/upload/chunk spans (%)",
             100.0 * (total - out["other"]) / total if total else 0.0)
    ctx.idle_ns = out
    return out


def idle_s(ctx, key: str) -> float | None:
    """Chip idle seconds per simulation cell charged to ``key``, averaged
    over the cell's chips."""
    out = idle_by_span(ctx)
    if out is None or not ctx.cells:
        return None
    return out[key] / 1e9 / len(ctx.devices) / ctx.cells
