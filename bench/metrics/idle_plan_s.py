"""idle_plan_s (s): chip idle time per simulation cell, averaged over the
cell's chips, inside the completed calls' ``bench.call`` spans while the
innermost ``repro.*`` host span open is ``repro.plan`` (host planning)
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.idle_s(ctx, "plan")
