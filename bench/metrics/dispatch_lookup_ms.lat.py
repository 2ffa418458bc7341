"""Mean ``serve.dispatch.lookup`` span, in ms (``bench/phases.py``)."""
from bench.phases import phase_ms


def read(ctx):
    return phase_ms(ctx, "lookup")
