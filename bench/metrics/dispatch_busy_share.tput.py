"""Summed ``serve.dispatch`` spans over window x replicas: how much of the
time each replica's dispatch thread was inside a dispatch."""


def read(ctx):
    spans = ctx.spans_named("serve.dispatch")
    if not spans:
        return None
    return sum(s.t_end - s.t_start for s in spans) / (ctx.seconds * ctx.chips)
