"""Real rows over dispatched bucket slots in the window (ServerStats)."""


def read(ctx):
    slots = ctx.stats["dispatched_slots"]
    return ctx.stats["real_rows"] / slots if slots else None
