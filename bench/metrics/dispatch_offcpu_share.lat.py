"""1 - CPU / wall seconds of the dispatch thread over every dispatch
phase but ``execute`` (``bench/phases.py``)."""
from bench.phases import offcpu_share


def read(ctx):
    return offcpu_share(ctx)
