"""plan_span_s (s): mean duration of the program's ``repro.plan`` host
span (``engine.make_plan``, one per simulation cell) in the completed
calls."""

from bench import scopes


def read(ctx):
    spans = scopes.spans(ctx, "repro.plan")
    if not spans:
        return None
    ctx.note("repro.plan spans / simulation cells", [len(spans), ctx.cells])
    return sum(e.dur for e in spans) / 1e9 / len(spans)
