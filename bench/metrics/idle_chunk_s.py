"""idle_chunk_s (s): chip idle time per simulation cell, averaged over
the cell's chips, inside the completed calls' ``bench.call`` spans while
the innermost ``repro.*`` host span open is ``repro.dispatch``,
``repro.sync`` or ``repro.finish`` (the chunk loop and result assembly)
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.idle_s(ctx, "chunk")
