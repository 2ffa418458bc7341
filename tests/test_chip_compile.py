"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode accepts blocks and VMEM footprints the chip's compiler
refuses, so these tests compile each kernel for a *described* v5e (the
TPU compiler runs without a chip attached) and check that a compiled
kernel launch is in the result.  Nothing runs: this guards compilation,
not numerics (tests/test_kernels.py covers those in interpret mode).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.precision import ComputeMode
from repro.kernels.conv_mapmajor import ops as conv_ops
from repro.kernels.conv_mapmajor.conv_mapmajor import (conv_mapmajor,
                                                       conv_mapmajor_int8)
from repro.kernels.matmul_mapmajor import ops as matmul_ops
from repro.kernels.matmul_mapmajor.matmul_mapmajor import (
    matmul_mapmajor, matmul_mapmajor_int8)

U = 128                                  # lane width: one channel group


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text   # a real kernel


def _compile_conv(sharding, *, h, cin, cout, k, stride=1, int8=False,
                  bias=True):
    """Compile one fused conv(+bias)+ReLU launch on an h x h SAME plane,
    shaped as the NCHW wrapper shapes it (padded, map-major)."""
    h_out, p0, p1 = conv_ops._pad_amounts(h, k, stride, "SAME")
    hp = h + p0 + p1
    gi, go = -(-cin // U), -(-cout // U)
    dt = jnp.int8 if int8 else jnp.bfloat16
    shapes = [((1, gi, hp, hp, U), dt), ((go, U, gi, k, k, U), dt)]
    vectors = (1 if int8 else 0) + (1 if bias else 0)
    shapes += [((go, U), jnp.float32)] * vectors
    kernel = conv_mapmajor_int8 if int8 else conv_mapmajor

    def fn(*a):
        return kernel(*a, stride=stride, out_hw=(h_out, h_out),
                      apply_relu=True, interpret=False)
    _compile(fn, *shapes, sharding=sharding)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("h,cin,cout,k", [
    (27, 64, 256, 3),      # SqueezeNet fire8_expand3x3: 2 output groups
    (27, 96, 256, 5),      # AlexNet conv2
    (13, 256, 384, 3),     # AlexNet conv3: 2 input x 3 output groups
    (56, 64, 192, 3),      # GoogLeNet conv2: the largest plane, 2 out groups
    (28, 16, 32, 5),       # GoogLeNet inc3a_5x5: 16 of 128 lanes in, 32 out
    (7, 192, 384, 3),      # GoogLeNet inc5b_3x3: 2 input x 3 output groups
], ids=["fire8_expand3x3", "alexnet_conv2", "alexnet_conv3",
        "googlenet_conv2", "googlenet_inc3a_5x5", "googlenet_inc5b_3x3"])
def test_fused_conv_multi_group_compiles(one_chip, h, cin, cout, k, int8):
    _compile_conv(one_chip, h=h, cin=cin, cout=cout, k=k, int8=int8)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_single_group_conv_compiles(one_chip, int8):
    _compile_conv(one_chip, h=28, cin=128, cout=128, k=3, int8=int8,
                  bias=False)


def _largest_admitted(k, stride, mode):
    """The largest square plane rule 1 admits for a u-wide stride-s conv
    under the default (v5e) budget: VMEM use grows with the plane, so it
    is the hardest admitted case."""
    h = k
    while conv_ops.fits_vmem(h + 1, h + 1, k, stride, "SAME", U, mode):
        h += 1
    assert h > k, "rule 1 admits no plane at all"
    return h


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("k", [3, 7])
def test_every_stride2_plane_rule1_admits_compiles(one_chip, k, int8):
    mode = ComputeMode.IMPRECISE_INT8 if int8 else ComputeMode.RELAXED
    h = _largest_admitted(k, 2, mode)
    _compile_conv(one_chip, h=h, cin=U, cout=U, k=k, stride=2, int8=int8)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_fc6_matmul_compiles(one_chip, int8):
    """AlexNet fc6: (batch, 9216) @ (9216, 4096), batch padded to a block."""
    m, kdim, n = 256, 9216, 4096
    if int8:
        _compile(lambda a, b, s, bias: matmul_mapmajor_int8(
                     a, b, s, bias, apply_relu=True, interpret=False),
                 ((m, kdim), jnp.int8), ((kdim, n), jnp.int8),
                 ((1, n), jnp.float32), ((1, n), jnp.float32),
                 sharding=one_chip)
    else:
        _compile(lambda a, b: matmul_mapmajor(a, b, interpret=False),
                 ((m, kdim), jnp.bfloat16), ((kdim, n), jnp.bfloat16),
                 sharding=one_chip)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_googlenet_fc_padded_matmul_compiles(one_chip, int8):
    """GoogLeNet fc: (32, 1024) @ (1024, 1000).  N = 1000 is no multiple
    of the 256-wide block, so the program runs it through the padding
    wrappers (the raw kernel refuses it)."""
    m, kdim, n = 32, 1024, 1000
    blocks = dict(bm=256, bn=256, bk=512, interpret=False)
    if int8:
        _compile(lambda a, w, ws, s, b: matmul_ops._matmul_padded_int8(
                     a, w, ws, s, b, relu=False, **blocks),
                 ((m, kdim), jnp.float32), ((kdim, n), jnp.int8),
                 ((n,), jnp.float32), ((), jnp.float32),
                 ((n,), jnp.float32), sharding=one_chip)
    else:
        _compile(lambda a, w: matmul_ops._matmul_padded(
                     a, w, ComputeMode.RELAXED, **blocks),
                 ((m, kdim), jnp.bfloat16), ((kdim, n), jnp.bfloat16),
                 sharding=one_chip)
