"""Traffic generator kinds, one module each, found by the traffic file's
``kind``: a module here named ``<kind>.py`` exposes ``buckets(traffic,
max_batch, replicas)`` and ``run(...)`` as ``closed.py`` does."""
