"""jit'd wrapper for the lock_grant kernel.

``grant_sorted`` runs the kernel on entries already in (key, enq) order:
padding to the block size and the row-major ``(rows, 128)`` tile layout
the kernel scans. ``lock_grant`` wraps it in the grant pass's sort and
unsort (``core.lockgrant.sort_requests`` / ``unsort``, shared with the
engine) and the XLA-side segment-total broadcast (contender counts), so
callers see the same contract as ``repro.core.lockgrant.grant_round``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.lockgrant import (
    KEY_SENTINEL,
    REQ_NONE,
    _segment_broadcast_last,
    sort_requests,
    unsort,
)
from repro.kernels import resolve_interpret
from repro.kernels.lock_grant.kernel import lock_grant_kernel
from repro.kernels.segscan import LANES, tile_block


def lock_grant(keys, ts, kind, write_holder, read_count, *, num_records,
               block_n=1024, interpret=None):
    """Drop-in twin of ``core.lockgrant.grant_round`` (grant, contenders).

    ``block_n`` is rounded up to whole ``(8, 128)`` tiles.
    ``interpret=None`` resolves backend-aware (compiled Pallas on
    TPU/GPU, interpreter on CPU); see ``repro.kernels.resolve_interpret``.
    """
    return _lock_grant_jit(
        keys, ts, kind, write_holder, read_count, num_records=num_records,
        block_n=block_n, interpret=resolve_interpret(interpret),
    )


def grant_sorted(keys, kind, wh_free, rc, *, block_n, interpret=None):
    """The kernel over entries sorted by (key, enq): ``(grant, op_pos)``
    in that order, each of the entries' length.

    The entries are padded to whole blocks of ``block_n`` (rounded up to
    ``(8, 128)`` tiles) with inactive sentinel entries, which sort last
    and start segments of their own.
    """
    block_n = tile_block(block_n)
    n = keys.shape[0]
    pad = (-n) % block_n
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), KEY_SENTINEL, keys.dtype)])
        kind = jnp.concatenate([kind, jnp.full((pad,), REQ_NONE, kind.dtype)])
        wh_free = jnp.concatenate([wh_free, jnp.zeros((pad,), wh_free.dtype)])
        rc = jnp.concatenate([rc, jnp.zeros((pad,), rc.dtype)])
    tiles = [x.reshape(-1, LANES) for x in (keys, kind, wh_free, rc)]
    grant, _req_pos, _wbefore, op_pos = lock_grant_kernel(
        *tiles, block_rows=block_n // LANES,
        interpret=resolve_interpret(interpret),
    )
    return grant.reshape(-1)[:n], op_pos.reshape(-1)[:n]


@functools.partial(
    jax.jit, static_argnames=("num_records", "block_n", "interpret")
)
def _lock_grant_jit(keys, ts, kind, write_holder, read_count, *, num_records,
                    block_n, interpret):
    e = sort_requests(keys, ts, kind, write_holder, read_count, num_records)
    grant, op_pos = grant_sorted(e.keys, e.kind, e.wh_free, e.rc,
                                 block_n=block_n, interpret=interpret)
    # segment totals (contenders) from the kernel's prefix op counts
    active = e.kind != REQ_NONE
    seg_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), e.keys[1:] != e.keys[:-1]]
    ) | ~active
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    contenders = _segment_broadcast_last(op_pos, seg_id)
    return (unsort(grant, e.index),
            unsort(jnp.where(active, contenders, 0), e.index))
