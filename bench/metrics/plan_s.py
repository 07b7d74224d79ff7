"""plan_s (s): host time per call of the program's ``make_plan``
(engine.py), which plans one simulation cell, from the profiler's Python
tracer frames that lie wholly inside the traced window."""

from bench import trace


def read(ctx):
    frames = trace.frames(ctx.trace, "make_plan", "engine.py")
    if not frames:
        return None
    return sum(e.dur for e in frames) / 1e9 / len(frames)
