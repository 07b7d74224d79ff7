"""stage_wavefront_us (us): device time per loop iteration of the round
step's ``stage.wavefront`` scope (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.stage_us(ctx, "wavefront")
