"""ORTHRUS: lock requests go through the concurrency-control lanes in
ascending lane order (lane of a key = its partition mod n_cc), by key
within a lane; padding last."""

from . import SENTINEL, reorder

FAMILY = "lock"
ACQUIRE = "cc_lanes"
DEADLOCK = "none"


def plan(w, cfg):
    if cfg.n_cc < 1:
        raise ValueError("orthrus needs n_cc >= 1")
    return reorder(w, lambda key, part: (
        (1, 0, key) if key == SENTINEL else (0, part % cfg.n_cc, key)))
