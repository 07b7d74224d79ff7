"""lock_grant_roofline (%): the least time the lock_grant kernel's bytes
take at the chip's peak HBM rate, over the kernel's device time.

The kernel reads the (key, enq)-sorted entry tiles (int32 key, int32
kind, bool write-free, int32 read count) and writes a bool grant tile and
three int32 prefix tiles. Compiled for the TPU, all eight tiles are s32:
the bool input is converted before the custom call and the bool output
is an s32 tile (its HLO reads ``s32[rows,128]`` throughout). ORTHRUS passes one entry per (slot, key
column): T * K entries, padded to a whole number of blocks of
min(8192, T * K) entries rounded up to (8, 128) tiles. The memory bound
is the roofline."""

KERNEL = "lock_grant"
TILE = 8 * 128


def entries(T: int, K: int) -> int:
    n = T * K
    block = max(TILE, -(-min(8 * TILE, n) // TILE) * TILE)
    return -(-n // block) * block


def bytes_per_call(T: int, K: int) -> int:
    """HBM bytes of one kernel call for T slots of K key columns."""
    return entries(T, K) * 4 * (4 + 4)


def call_bytes(cell) -> int | None:
    if cell.protocol != "orthrus":
        return None
    return bytes_per_call(cell.slots, cell.meta.max_keys)


def read(ctx):
    return ctx.kernel_roofline(KERNEL, call_bytes)
