"""Lock-grant pass of the reference's lock loop, over request entries
sorted by (record, enqueue stamp).

Per record, in enqueue order (FIFO, no writer starvation):

* a read is granted when the record has no write holder and no write
  request is queued ahead of it (reads share);
* a write is granted when the record has no write holder and no read
  holders, and it is the first request queued;
* a release entry is never granted; it counts, as every entry does,
  among the record's lock-table operations of the round (its
  contenders), which the coherence cost model charges.

Written plainly with segment sums over runs of equal keys; each padding
entry is a run of its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

REQ_READ = 0
REQ_WRITE = 1
REQ_RELEASE = 2
REQ_NONE = 3  # inactive slot (padding)

KEY_SENTINEL = jnp.iinfo(jnp.int32).max


def lex_order(primary, secondary):
    """Indices sorting by (primary, secondary), ties in original order."""
    by_second = jnp.argsort(secondary, stable=True)
    return by_second[jnp.argsort(primary[by_second], stable=True)]


def inverse_permutation(order):
    return jnp.argsort(order)


def runs(keys, breaks=None):
    """Run id of each sorted entry: a new run where the key changes, or
    where ``breaks`` is set."""
    start = jnp.concatenate([jnp.ones((1,), jnp.bool_), keys[1:] != keys[:-1]])
    if breaks is not None:
        start = start | breaks
    return jnp.cumsum(start.astype(jnp.int32)) - 1


def _run_total(x, run):
    return jax.ops.segment_sum(x, run, num_segments=x.shape[0])[run]


def run_before(x, run):
    """Sum of ``x`` over the entries of the same run before each entry."""
    n = x.shape[0]
    incl = jnp.cumsum(x)
    first = jax.ops.segment_min(jnp.arange(n, dtype=jnp.int32), run,
                                num_segments=n)[run]
    return incl - x - (incl - x)[first]


def segmented_grant(keys, ts, kind, wh_free, rc, weight=None):
    """Grant decisions over entries sorted by (key, ts).

    Returns (grant bool[N], contenders int32[N] (0 for padding), segment
    sum of ``weight`` int32[N] (zeros without one)).
    """
    del ts  # the sort order carries it
    active = kind != REQ_NONE
    is_read = kind == REQ_READ
    is_write = kind == REQ_WRITE
    run = runs(keys, ~active)
    reqs_before = run_before((is_read | is_write).astype(jnp.int32), run)
    writes_before = run_before(is_write.astype(jnp.int32), run)
    grant = (is_read & wh_free & (writes_before == 0)) | (
        is_write & wh_free & (rc == 0) & (reqs_before == 0))
    contenders = jnp.where(active,
                           _run_total(active.astype(jnp.int32), run), 0)
    wsum = (jnp.zeros_like(contenders) if weight is None
            else _run_total(weight, run))
    return grant, contenders, wsum
