"""relayout_share: share of the device's busy time in which no Mosaic kernel
ran (the XLA relayouts, pads, slices and the head around the kernels), in %."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * (ctx.trace.busy_s - ctx.trace.kernel_busy_s) / ctx.trace.busy_s
