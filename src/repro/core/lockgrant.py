"""Segmented FIFO lock-grant primitive.

This is the hot inner loop of every lock manager in the paper: given the set
of outstanding lock requests this round, decide which are granted, honoring

  * FIFO fairness per record (older enqueue timestamp first — no writer
    starvation: reads behind a waiting write are NOT granted),
  * read sharing (multiple reads granted together),
  * write exclusivity (a write is granted only when it is the oldest waiter
    and the record has no read holders),

and report per-request *contender counts* (how many lock-table operations
touched the same record this round), which drive the cache-coherence cost
model for shared-memory lock tables.

``segmented_grant`` operates on **pre-sorted** request arrays and is the
contract implemented by the Pallas kernel in ``repro.kernels.lock_grant``
(this jnp version is its oracle). ``sort_requests`` and ``unsort`` take a
round's requests into (key, ts) order and back, with one sort each way;
the engine's ORTHRUS grant pass and the kernel's wrapper share them.
``grant_round`` is the plain sort / decide / unsort by gathers, kept as
the oracle of both.

Entry types: ``REQ_READ`` / ``REQ_WRITE`` are grantable requests;
``REQ_RELEASE`` entries participate in contender counting only (a release is
a lock-table op on the same cache line) and are never granted.

All arithmetic is int32 so the primitive works without jax_enable_x64;
sorting by (key, ts) uses two stable argsorts instead of a packed composite.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

REQ_READ = 0
REQ_WRITE = 1
REQ_RELEASE = 2
REQ_NONE = 3  # inactive slot (padding)

KEY_SENTINEL = jnp.iinfo(jnp.int32).max
_I32_MIN = jnp.iinfo(jnp.int32).min


def lex_order(primary, secondary):
    """Indices sorting by (primary, secondary), both int32, stable.

    A single two-key ``lax.sort`` carrying an iota: identical permutation
    to the classic two-pass stable argsort (ties in (primary, secondary)
    keep original order) at roughly half the cost — sorts are the hottest
    ops in the engine's round loop.
    """
    return lex_sort(primary, secondary)[-1]


def lex_sort(primary, secondary, *payloads):
    """``lex_order``'s stable sort by (primary, secondary), carrying
    ``payloads`` and the entry index ``iota`` through it.

    Returns ``(primary, secondary, *payloads, index)`` in sorted order,
    where ``index`` is ``lex_order(primary, secondary)`` itself: the
    sort delivers its operands sorted, with no gather by the order.
    """
    iota = jnp.arange(primary.shape[0], dtype=jnp.int32)
    return jax.lax.sort(
        (primary, secondary, *payloads, iota), dimension=-1,
        num_keys=2, is_stable=True,
    )


class SortedRequests(NamedTuple):
    """Grant-pass entries in (key, ts) order, with the lock table read at
    their keys (``sort_requests``)."""

    keys: jax.Array  # int32[N] sorted keys
    ts: jax.Array  # int32[N] enqueue stamps
    kind: jax.Array  # int32[N] REQ_* kinds
    index: jax.Array  # int32[N] entry index in request order
    in_range: jax.Array  # bool[N] key < num_records
    holder: jax.Array  # int32[N] the key's write holder (-1: none)
    wh_free: jax.Array  # bool[N] in range and no write holder
    rc: jax.Array  # int32[N] the key's read count (0 out of range)


def sort_requests(keys, ts, kind, write_holder, read_count, num_records):
    """Sort requests once by (key, ts), carrying kind and entry index, and
    read the ``[num_records]`` lock-table columns at the sorted keys.

    Everything the grant pass needs comes out in sorted order: one sort
    and the two lock-table gathers, no permutation gathers. ``unsort``
    takes a sorted result back to request order.
    """
    ks, ts_s, kind_s, index = lex_sort(keys, ts, kind)
    safe = jnp.minimum(ks, num_records - 1)
    in_range = ks < num_records
    holder = write_holder[safe]
    return SortedRequests(
        keys=ks, ts=ts_s, kind=kind_s, index=index, in_range=in_range,
        holder=holder, wh_free=(holder == -1) & in_range,
        rc=jnp.where(in_range, read_count[safe], 0),
    )


def unsort(x_sorted, index):
    """Put values in ``sort_requests`` order back into request order:
    ``out[index[i]] = x_sorted[i]``, one sort keyed by the entry index
    (a permutation: no ties, so the sort need not be stable)."""
    return jax.lax.sort(
        (index, x_sorted), dimension=-1, num_keys=1, is_stable=False
    )[1]


def inverse_permutation(order):
    """Inverse of a permutation via scatter — equivalent to
    ``jnp.argsort(order)`` (whose stable sort of unique values *is* the
    inverse) without paying for a sort."""
    n = order.shape[0]
    return (
        jnp.zeros((n,), order.dtype)
        .at[order]
        .set(jnp.arange(n, dtype=order.dtype))
    )


def segmented_grant(keys, ts, kind, wh_free, rc, weight=None):
    """Grant decisions over requests sorted by (key, ts).

    Args:
      keys:    int32[N] record ids, sorted ascending; KEY_SENTINEL = padding.
      ts:      int32[N] enqueue stamps, ascending within each key segment.
      kind:    int32[N] REQ_* entry kind.
      wh_free: bool[N]  per-entry: record has no write holder (post-release).
      rc:      int32[N] per-entry: record's current read-holder count.
      weight:  optional int32[N] per-entry weight to segment-sum (e.g. "is a
               new lock-table op this round", for line-occupancy costing).

    Returns:
      grant:      bool[N]  request granted this round.
      contenders: int32[N] number of lock-table ops on this record this round.
      wsum:       int32[N] segment sum of `weight` (zeros if weight is None).
    """
    active = kind != REQ_NONE
    is_req = active & ((kind == REQ_READ) | (kind == REQ_WRITE))
    is_write_req = active & (kind == REQ_WRITE)
    is_read_req = active & (kind == REQ_READ)

    # Segment structure over sorted keys (each padding entry is its own seg).
    seg_start = (
        jnp.concatenate([jnp.ones((1,), jnp.bool_), keys[1:] != keys[:-1]])
        | ~active
    )
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1

    def seg_cumsum(x):
        """Inclusive segmented cumsum of int32 x along the sorted order."""
        total = jnp.cumsum(x)
        base = jax.lax.cummax(
            jnp.where(seg_start, total - x, _I32_MIN)
        )
        return total - base

    req_pos_incl = seg_cumsum(is_req.astype(jnp.int32))  # 1-based among reqs
    write_seen_incl = seg_cumsum(is_write_req.astype(jnp.int32))
    writes_before = write_seen_incl - is_write_req.astype(jnp.int32)

    # Read grant: record write-free and no older write request queued ahead.
    grant_read = is_read_req & wh_free & (writes_before == 0)
    # Write grant: record write-free, zero read holders, oldest in segment.
    grant_write = is_write_req & wh_free & (rc == 0) & (req_pos_incl == 1)
    grant = (grant_read | grant_write) & active

    contenders = _segment_broadcast_last(
        seg_cumsum(active.astype(jnp.int32)), seg_id
    )
    if weight is None:
        wsum = jnp.zeros_like(contenders)
    else:
        wsum = _segment_broadcast_last(seg_cumsum(weight), seg_id)
    return grant, jnp.where(active, contenders, 0), wsum


def _segment_broadcast_last(inclusive, seg_id):
    """Broadcast each segment's last inclusive value to all its members."""
    n = inclusive.shape[0]
    last_of_seg = jnp.concatenate(
        [seg_id[1:] != seg_id[:-1], jnp.ones((1,), jnp.bool_)]
    )
    seg_last_val = (
        jnp.zeros((n,), inclusive.dtype)
        .at[jnp.where(last_of_seg, seg_id, n - 1)]
        .max(jnp.where(last_of_seg, inclusive, 0))
    )
    return seg_last_val[seg_id]


def segment_sum_by_key(keys, weight):
    """Per-entry sum of `weight` over entries sharing the same key."""
    order = jnp.argsort(keys, stable=True)
    inv = inverse_permutation(order)
    ks = keys[order]
    seg_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), ks[1:] != ks[:-1]]
    )
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    total = jnp.cumsum(weight[order])
    base = jax.lax.cummax(
        jnp.where(seg_start, total - weight[order], _I32_MIN)
    )
    return _segment_broadcast_last(total - base, seg_id)[inv]


def segment_sum_sorted(keys_sorted, weight_sorted):
    """Per-entry segment sum of ``weight_sorted`` over runs of equal
    ``keys_sorted`` (already sorted). The engine reuses its grant-pass
    sort order to avoid re-sorting by key."""
    seg_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), keys_sorted[1:] != keys_sorted[:-1]]
    )
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    total = jnp.cumsum(weight_sorted)
    base = jax.lax.cummax(
        jnp.where(seg_start, total - weight_sorted, _I32_MIN)
    )
    return _segment_broadcast_last(total - base, seg_id)


def grant_round(keys, ts, kind, write_holder, read_count, num_records,
                weight=None):
    """Engine-facing grant pass: sorts, decides, unsorts.

    Returns (grant, contenders, wsum) in the original request order.
    """
    safe = jnp.minimum(keys, num_records - 1)
    in_range = keys < num_records
    wh_free = (write_holder[safe] == -1) & in_range
    rc = jnp.where(in_range, read_count[safe], 0)

    order = lex_order(keys, ts)
    inv = inverse_permutation(order)
    w = None if weight is None else weight[order]
    g, c, ws = segmented_grant(
        keys[order], ts[order], kind[order], wh_free[order], rc[order], w
    )
    return g[inv], c[inv], ws[inv]
