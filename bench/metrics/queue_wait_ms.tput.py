"""Mean wait of a request from enqueue to its bucket's release, in ms:
``queue_s`` of the ``serve.request`` spans (``bench/phases.py``)."""
from bench.phases import queue_wait_ms


def read(ctx):
    return queue_wait_ms(ctx)
