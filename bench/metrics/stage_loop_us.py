"""stage_loop_us (us): device time per loop iteration of the chunk
runner's leaf ops outside every ``stage.*`` scope: the round loop, column
unpack and repack, carry copies (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.stage_us(ctx, "")
