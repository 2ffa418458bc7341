"""Images whose result reached the client inside the window, per second."""


def read(ctx):
    done = sum(1 for s in ctx.requests
               if s.ok and s.t_done is not None
               and ctx.t_open <= s.t_done <= ctx.t_close)
    return done / ctx.seconds
