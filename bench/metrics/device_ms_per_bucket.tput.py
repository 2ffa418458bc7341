"""Device busy time in the traced window (summed over chips) per bucket
dispatched in the window."""


def read(ctx):
    if ctx.reduced is None or not ctx.reduced.busy_s \
            or not ctx.stats["batches"]:
        return None
    busy = sum(ctx.reduced.busy_s.values())
    return 1e3 * busy / ctx.stats["batches"]
