"""DGCC: each batch of ``batch_epoch`` transactions runs over its
record-level dependency graph, in serial (submission) order. The
planner sees the full access set, so OLLP reconnaissance is charged but
an estimate never misses at execution."""

from . import conflict_preds, program_order

FAMILY = "batch"


def plan(w, cfg):
    p = program_order(w, ollp_miss=False)
    p.epoch = w.batch_epoch
    p.preds = conflict_preds(w, w.batch_epoch)
    return p
