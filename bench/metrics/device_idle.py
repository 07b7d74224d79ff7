"""device_idle (%): the share of the traced window in which no operation
ran on a chip, averaged over the cell's chips; a chip with no plane in
the trace ran none and is idle throughout. Busy time is the union of the
intervals of the device's operation events."""

from bench import trace


def read(ctx):
    span = ctx.trace.hi - ctx.trace.lo
    if not ctx.devices or span <= 0:
        return None
    shares = [1.0 - trace.busy_ns(ctx.trace, d) / span for d in ctx.devices]
    shares += [1.0] * (ctx.chips - len(shares))
    ctx.note("device_idle per chip (%)", [100.0 * s for s in shares])
    return 100.0 * sum(shares) / len(shares)
