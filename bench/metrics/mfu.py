"""mfu: dense-equivalent FLOPs of the images completed in the window (conv
stages and head, ``work.image_flops``) over the window times the chip's bf16
peak, in %."""

from bench import work


def read(ctx):
    if ctx.trace is None or not ctx.images:
        return None
    flops = ctx.images * work.image_flops(ctx.conf)
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["bf16_flops_per_s"])
