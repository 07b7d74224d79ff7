"""dep_wavefront_roofline (%): the least time the dep_wavefront kernel's
bytes take at the chip's peak HBM rate, over the kernel's device time.

The kernel reads the dst-sorted edge tiles (int32 dst, bool src_ok) and
writes two int32 tiles (prefix miss and position counts). Compiled for
the TPU, every one of the four tiles is s32: the bool tile is converted
before the custom call (its HLO operands read ``s32[rows,128]``). The batch
engine passes one edge per (slot, predecessor column): T * P entries,
padded to a whole number of blocks of min(8192, T * P) entries rounded up
to (8, 128) tiles. The kernel does no arithmetic worth a FLOP bound, so
its roofline is the memory bound."""

KERNEL = "dep_wavefront"
TILE = 8 * 128


def entries(T: int, P: int) -> int:
    n = T * P
    block = max(TILE, -(-min(8 * TILE, n) // TILE) * TILE)
    return -(-n // block) * block


def bytes_per_call(T: int, P: int) -> int:
    """HBM bytes of one kernel call for T slots of P predecessors."""
    return entries(T, P) * 4 * (2 + 2)


def call_bytes(cell) -> int | None:
    if cell.protocol not in ("dgcc", "quecc") or cell.meta.pred_width == 0:
        return None
    return bytes_per_call(cell.slots, cell.meta.pred_width)


def read(ctx):
    return ctx.kernel_roofline(KERNEL, call_bytes)
