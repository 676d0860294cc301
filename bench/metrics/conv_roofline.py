"""conv_roofline: the least time the chip could spend on the conv stages of
the window's classify calls (per stage the larger of FLOPs over the bf16 peak
and bytes over HBM bandwidth, ``work.conv_ideal_s`` at the full batch) over
the Mosaic kernel time of those calls, in %.

It is read only where every call ran one Mosaic kernel per conv stage: a
stage that ran outside a kernel would count its work without its time."""

from bench import work


def read(ctx):
    calls = ctx.trace.calls if ctx.trace else []
    n_stages = len(ctx.conf["convs"])
    if not calls or any(c.n_kernels != n_stages for c in calls):
        return None
    ideal = len(calls) * work.conv_ideal_s(ctx.conf, ctx.max_batch, ctx.peak)
    return 100.0 * ideal / (sum(c.kernel_ns for c in calls) / 1e9)
