"""Mean ``serve.batch_wait`` span (oldest request's enqueue to release)."""


def read(ctx):
    spans = ctx.spans_named("serve.batch_wait")
    if not spans:
        return None
    return 1e3 * sum(s.t_end - s.t_start for s in spans) / len(spans)
