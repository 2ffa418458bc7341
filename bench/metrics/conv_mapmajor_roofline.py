"""Roofline share of the ``conv_mapmajor`` Pallas kernel, in percent:
the plan's Pallas conv groups, launched as ``_conv2d_mapmajor_pallas``
(``kernels/conv_mapmajor/ops.py``)."""
from bench.roofline import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "conv", ("_conv2d_mapmajor_pallas",
                                         "_conv2d_mapmajor_pallas_int8"))
