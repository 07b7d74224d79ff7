"""stage_line_cost_us (us): device time per loop iteration of the round
step's ``stage.line_cost`` scope (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.stage_us(ctx, "line_cost")
