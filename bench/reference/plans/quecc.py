"""QueCC: each batch runs over per-lane execution queues (lane of a key
= its partition mod n_cc); a transaction follows its predecessor in
every queue it joins."""

from . import program_order, queue_preds

FAMILY = "batch"


def plan(w, cfg):
    if cfg.n_cc < 1:
        raise ValueError("quecc needs n_cc >= 1")
    p = program_order(w, ollp_miss=False)
    p.epoch = w.batch_epoch
    p.preds = queue_preds(w, w.batch_epoch, cfg.n_cc)
    return p
