"""Share of the ``serve.dispatch`` spans with ``overlapped`` = 1: buckets
launched while another bucket of the same server was in flight, against 0
where none was (``serving_dispatch_overlap_total`` counts the same); None
where no span carries ``overlapped``, as in a program that dispatches one
bucket at a time."""


def read(ctx):
    overlapped = [s.attrs["overlapped"]
                  for s in ctx.spans_named("serve.dispatch")
                  if "overlapped" in s.attrs]
    return sum(overlapped) / len(overlapped) if overlapped else None
