"""conv_ms: Mosaic kernel device time inside one classify program run,
averaged over the runs inside the window, in ms."""


def read(ctx):
    calls = ctx.trace.calls if ctx.trace else []
    if not calls or not any(c.n_kernels for c in calls):
        return None
    return sum(c.kernel_ns for c in calls) / len(calls) / 1e6
