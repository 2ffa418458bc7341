"""Mean ``serve.dispatch.copy_out`` span, in ms (``bench/phases.py``)."""
from bench.phases import phase_ms


def read(ctx):
    return phase_ms(ctx, "copy_out")
