"""Deadlock-free locking: every lock taken up front in ascending key
order (ties in the transaction's own order), then the ops."""

from . import reorder

FAMILY = "lock"
ACQUIRE = "upfront"
DEADLOCK = "none"


def plan(w, cfg):
    return reorder(w, lambda key, part: key)
