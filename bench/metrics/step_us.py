"""step_us (us): device time of the chunk runner's executions per loop
iteration. The runner is the jitted ``run_chunk`` of the sweep driver
(module ``jit_run_chunk``); its time in the calls that completed inside
the traced window is summed over the cell's chips and divided by the
iterations the chips executed in those calls: per call, group and chip,
the most executed steps of any lane that chip held (the ``steps``
counter of the results)."""

MODULE = "jit_run_chunk"


def read(ctx):
    ns = sum(e.dur for d in ctx.devices
             for e in ctx.in_calls(ctx.trace.modules(d))
             if e.name.startswith(MODULE))
    iters = ctx.loop_iterations()
    if ns == 0 or iters == 0:
        return None
    return ns / 1e3 / iters
