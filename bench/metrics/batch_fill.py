"""batch_fill: images the classify calls of the window carried, over the
rows they could carry (calls x max_batch), in %.  Counted by the harness."""


def read(ctx):
    if not ctx.calls:
        return None
    return 100.0 * ctx.rows / (ctx.calls * ctx.max_batch)
