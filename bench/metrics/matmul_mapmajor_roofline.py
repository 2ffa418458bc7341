"""Roofline share of the ``matmul_mapmajor`` Pallas kernel, in percent:
the plan's Pallas dense groups, launched as ``_matmul_padded``
(``kernels/matmul_mapmajor/ops.py``)."""
from bench.roofline import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "dense", ("_matmul_padded",
                                          "_matmul_padded_int8"))
