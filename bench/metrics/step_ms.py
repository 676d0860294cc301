"""step_ms: device time of one classify program run, averaged over the runs
inside the window, in ms.  From the trace's XLA Modules line."""


def read(ctx):
    calls = ctx.trace.calls if ctx.trace else []
    if not calls:
        return None
    return sum(c.end - c.start for c in calls) / len(calls) / 1e6
