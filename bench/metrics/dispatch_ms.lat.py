"""Mean ``serve.dispatch`` span (pad + execute + copy-out of one bucket)."""


def read(ctx):
    spans = ctx.spans_named("serve.dispatch")
    if not spans:
        return None
    return 1e3 * sum(s.t_end - s.t_start for s in spans) / len(spans)
