"""Dynamic two-phase locking with waitdie deadlock handling: each lock is
requested in the transaction's own order, right before its op; no access
analysis, so no OLLP reconnaissance."""

from . import program_order

FAMILY = "lock"
ACQUIRE = "interleaved"
DEADLOCK = "waitdie"


def plan(w, cfg):
    return program_order(w, ollp=False, ollp_miss=False)
