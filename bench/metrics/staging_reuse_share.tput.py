"""Share of the ``serve.dispatch.assemble`` spans whose bucket was copied
into the server's reused staging buffer (``staged`` = 1, against 0 where
the dispatch allocated a buffer); None where no span carries ``staged``,
as in a program without a staging buffer."""


def read(ctx):
    staged = [s.attrs["staged"]
              for s in ctx.spans_named("serve.dispatch.assemble")
              if "staged" in s.attrs]
    return sum(staged) / len(staged) if staged else None
