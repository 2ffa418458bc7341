"""1 - union of device-operation intervals / window, averaged over chips."""


def read(ctx):
    if ctx.reduced is None or not ctx.reduced.busy_s:
        return None
    return ctx.reduced.idle_share
