"""Pallas TPU kernel: OLP direct convolution on map-major data.

This is the paper's hot loop (Fig. 6) adapted to the TPU memory hierarchy:

  * Thread-level OLP (§IV-A): each grid cell owns an output tile — one
    (batch, output-channel-group) pair — and performs the *entire*
    Cin x Kh x Kw reduction locally in a VMEM f32 scratch accumulator.
    No cross-cell reduction exists, exactly the property the paper uses to
    pick OLP over KLP/FLP.
  * Intra-thread vectorized MAC (§IV-B): operands are map-major, so the
    u-wide channel group sits in the TPU lane dimension; each (kh, kw)
    step is a (pixels, u_in) @ (u_in, u_out) dot on the MXU — the paper's
    u-way vector MAC with u = 128.
  * Zero-overhead dynamic reordering (§IV-B-1): the output BlockSpec writes
    (N, Go, Ho, Wo, u) directly — map-major — so the next layer consumes it
    with no relayout, the Eqs. (3)-(5) trick expressed as a block layout.

Grid: (N, Go, Gi); the innermost Gi dimension accumulates input-channel
groups into the revisited output block (standard TPU sequential-grid
accumulation).  Each tap reads its patch straight from the VMEM input
block.  A stride-s convolution first splits the padded plane into its s*s
phases (rows p, p+s, ... and columns q, q+s, ...), so that every tap is a
contiguous stride-1 read of one phase: Mosaic has no strided load for
16- or 8-bit data.

VMEM envelope: the input block holds one batch element's full padded
spatial extent for one channel group.  :func:`conv_vmem_bytes` counts what
one grid step holds — the double-buffered input, weight and output blocks,
the accumulator and the per-tap temporaries, each padded to whole VMEM
tiles — and the kernel asks the compiler for exactly that much scoped
VMEM.  ops.py admits a layer only when that count fits the device
profile's budget, and falls back to the XLA path above it.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret
from ...core.precision import ComputeMode

#: Scoped VMEM the compiler needs beyond the buffers
#: :func:`conv_vmem_bytes` counts (its own internal scratch).
_VMEM_HEADROOM = 4 * 1024 * 1024


def _tile_bytes(shape, dtype) -> int:
    """VMEM bytes of one buffer: the trailing pair is padded to whole
    (sublane, lane) tiles — 8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit
    data by 128 lanes."""
    itemsize = jnp.dtype(dtype).itemsize
    rows_per_tile = 8 * 4 // itemsize
    *lead, rows, lanes = shape
    return (math.prod(lead) * -(-rows // rows_per_tile) * rows_per_tile
            * -(-lanes // 128) * 128 * itemsize)


def phase_extent(out: int, k: int, stride: int) -> int:
    """Rows (or columns) of one stride phase that the taps of a k-wide,
    stride-s window read for ``out`` outputs."""
    return out + (k - 1) // stride


def conv_vmem_bytes(*, h_out: int, w_out: int, kh: int, kw: int,
                    stride: int, u: int, u_out: int, operand_dtype,
                    acc_dtype, out_dtype) -> int:
    """Scoped VMEM one grid step of :func:`conv_mapmajor` holds."""
    hp, wp = phase_extent(h_out, kh, stride), phase_extent(w_out, kw, stride)
    pixels = h_out * w_out
    blocks = (_tile_bytes((stride * stride, hp, wp, u), operand_dtype)
              + _tile_bytes((u_out, kh, kw, u), operand_dtype)
              + _tile_bytes((h_out, w_out, u_out), out_dtype)
              + 2 * _tile_bytes((1, u_out), jnp.float32))
    acc = _tile_bytes((pixels, u_out), acc_dtype)
    # Live values inside one step: the running sum and one tap's product
    # (accumulator-sized each) and one tap's patch.
    temps = 2 * acc + _tile_bytes((pixels, u), operand_dtype)
    return 2 * blocks + acc + temps


def _split_phases(x_mm: jnp.ndarray, stride: int, hp: int,
                  wp: int) -> jnp.ndarray:
    """(N, Gi, H, W, u) -> (N, Gi * s * s, hp, wp, u): phase (p, q) of group
    g holds rows p, p+s, ... and columns q, q+s, ... of the padded plane.
    The plane is zero-padded or cropped to exactly (s*hp, s*wp) first."""
    n, n_gi, h, w, u = x_mm.shape
    s = stride
    x = x_mm[:, :, :s * hp, :s * wp]
    x = jnp.pad(x, ((0, 0), (0, 0), (0, s * hp - x.shape[2]),
                    (0, s * wp - x.shape[3]), (0, 0)))
    if s == 1:
        return x
    x = x.reshape(n, n_gi, hp, s, wp, s, u).transpose(0, 1, 3, 5, 2, 4, 6)
    return x.reshape(n, n_gi * s * s, hp, wp, u)


def _conv_kernel(x_ref, w_ref, *refs, kh: int, kw: int,
                 stride: int, h_out: int, w_out: int, n_gi: int,
                 out_dtype, acc_dtype, has_scale: bool, has_bias: bool,
                 apply_relu: bool):
    """One grid cell: accumulate one input-channel group into the output tile.

    x_ref: (1, s*s, hp, wp, u_in)       one batch elem, one input group,
                                        split into its stride phases
    w_ref: (1, u_out, 1, kh, kw, u_in)  weights for this (go, gi) pair
    s_ref: (1, u_out)                   optional dequant scale (has_scale):
                                        act_scale * per-output-channel
                                        weight scale, int8 datapath only
    b_ref: (1, u_out)                   optional bias block (has_bias)
    o_ref: (1, 1, h_out, w_out, u_out)  revisited across the gi grid dim
    acc_ref: VMEM scratch (h_out * w_out, u_out) in acc_dtype

    The fused epilogue (§IV-B meets Motamedi et al.'s folded post-conv
    computation) runs at flush time on the VMEM accumulator: dequant (int8
    datapath), bias add and ReLU happen in-register before the single
    output write, so a conv+bias+ReLU group is one launch with zero extra
    HBM traffic.  On the int8 datapath the operands are int8, ``acc_dtype``
    is int32 (``preferred_element_type=jnp.int32`` keeps the MXU MACs
    exact), and the flush rescales the int32 accumulator to float.
    """
    refs = list(refs)
    s_ref = refs.pop(0) if has_scale else None
    b_ref = refs.pop(0) if has_bias else None
    o_ref, acc_ref = refs
    gi = pl.program_id(2)

    @pl.when(gi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    u_in = x_ref.shape[-1]
    u_out = o_ref.shape[-1]

    acc = acc_ref[...]
    for dh in range(kh):
        for dw in range(kw):
            # Output (i, j) of tap (dh, dw) reads padded row dh + s*i, i.e.
            # row dh//s + i of phase dh % s (likewise for columns).
            phase = (dh % stride) * stride + dw % stride
            patch = x_ref[0, phase, pl.ds(dh // stride, h_out),
                          pl.ds(dw // stride, w_out), :]
            patch = patch.reshape(h_out * w_out, u_in)
            wk = w_ref[0, :, 0, dh, dw, :]          # (u_out, u_in)
            acc = acc + jax.lax.dot_general(
                patch, wk, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dtype)
    acc_ref[...] = acc

    @pl.when(gi == n_gi - 1)
    def _flush():
        out = acc_ref[...]                          # (h_out*w_out, u_out)
        if has_scale:
            out = out.astype(jnp.float32) * s_ref[...]
        if has_bias:
            out = out + b_ref[...].astype(out.dtype)
        if apply_relu:
            out = jnp.maximum(out, 0)
        o_ref[0, 0] = out.reshape(h_out, w_out, u_out).astype(out_dtype)


def _conv_call(x_mm, w_mm, s_mm, b_mm, *, stride, out_hw, operand_dtype,
               acc_dtype, out_dtype, apply_relu, interpret):
    """Shared launch of both datapaths (``s_mm`` is the int8 dequant)."""
    n, n_gi, h_pad, w_pad, u = x_mm.shape
    n_go, u_out, n_gi2, kh, kw, u2 = w_mm.shape
    assert n_gi == n_gi2 and u == u2, (x_mm.shape, w_mm.shape)
    if out_hw is None:
        h_out = (h_pad - kh) // stride + 1
        w_out = (w_pad - kw) // stride + 1
    else:
        h_out, w_out = out_hw
    assert h_pad >= (h_out - 1) * stride + kh, "pad input to (out-1)*s+k"
    assert w_pad >= (w_out - 1) * stride + kw, "pad input to (out-1)*s+k"
    hp = phase_extent(h_out, kh, stride)
    wp = phase_extent(w_out, kw, stride)
    phases = stride * stride

    def channel_vector(v_mm):
        # (Go, u_out) -> (Go, 1, u_out): one group's (1, u_out) block is
        # then a whole trailing pair, which Mosaic accepts for any Go (a
        # (1, u_out) block of the 2-D array is refused once Go > 1).
        assert v_mm.shape == (n_go, u_out), (v_mm.shape, (n_go, u_out))
        return v_mm.astype(jnp.float32)[:, None, :]

    vector_spec = pl.BlockSpec((None, 1, u_out), lambda b, go, gi: (go, 0, 0))
    in_specs = [
        pl.BlockSpec((1, phases, hp, wp, u),
                     lambda b, go, gi: (b, gi, 0, 0, 0)),
        pl.BlockSpec((1, u_out, 1, kh, kw, u),
                     lambda b, go, gi: (go, 0, gi, 0, 0, 0)),
    ]
    operands = [_split_phases(x_mm.astype(operand_dtype), stride, hp, wp),
                w_mm.astype(operand_dtype)]
    for v in (s_mm, b_mm):
        if v is not None:
            in_specs.append(vector_spec)
            operands.append(channel_vector(v))

    kernel = functools.partial(
        _conv_kernel, kh=kh, kw=kw, stride=stride, h_out=h_out, w_out=w_out,
        n_gi=n_gi, out_dtype=out_dtype, acc_dtype=acc_dtype,
        has_scale=s_mm is not None, has_bias=b_mm is not None,
        apply_relu=apply_relu)
    vmem = conv_vmem_bytes(h_out=h_out, w_out=w_out, kh=kh, kw=kw,
                           stride=stride, u=u, u_out=u_out,
                           operand_dtype=operand_dtype, acc_dtype=acc_dtype,
                           out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(n, n_go, n_gi),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, h_out, w_out, u_out),
                               lambda b, go, gi: (b, go, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n_go, h_out, w_out, u_out),
                                       out_dtype),
        scratch_shapes=[pltpu.VMEM((h_out * w_out, u_out), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + _VMEM_HEADROOM),
        interpret=resolve_interpret(interpret),
    )(*operands)


def conv_mapmajor(x_mm: jnp.ndarray, w_mm: jnp.ndarray,
                  b_mm: jnp.ndarray = None, *, stride: int = 1,
                  out_hw=None,
                  mode: ComputeMode = ComputeMode.RELAXED,
                  apply_relu: bool = False,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Map-major OLP convolution with an optional fused bias+ReLU epilogue.

    x_mm: (N, Gi, H_pad, W_pad, u)   map-major, already padded for SAME
    w_mm: (Go, u_out, Gi, Kh, Kw, u) map-major weights (synthesis-time order)
    b_mm: (Go, u_out) optional bias, group-blocked like the output channels
    returns (N, Go, Ho, Wo, u) map-major — directly consumable by the next
    layer (the zero-overhead reorder).

    ``b_mm``/``apply_relu`` fold the post-conv computation into the MAC
    launch (applied to the accumulator at flush time), so a fused
    conv+bias+ReLU group is exactly one Pallas launch.  ``interpret``
    defaults to the backend: compiled on a TPU, interpreted elsewhere.
    """
    return _conv_call(x_mm, w_mm, None, b_mm, stride=stride, out_hw=out_hw,
                      operand_dtype=mode.operand_dtype,
                      acc_dtype=mode.accum_dtype, out_dtype=mode.out_dtype,
                      apply_relu=apply_relu, interpret=interpret)


def conv_mapmajor_int8(x_mm: jnp.ndarray, w_mm: jnp.ndarray,
                       s_mm: jnp.ndarray, b_mm: jnp.ndarray = None, *,
                       stride: int = 1, out_hw=None,
                       apply_relu: bool = False,
                       out_dtype=jnp.bfloat16,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """The true int8 datapath: int8 x int8 -> int32 MACs with a fused
    dequant(+bias+ReLU) epilogue at flush — still exactly one Pallas launch.

    x_mm: (N, Gi, H_pad, W_pad, u)   int8 map-major activations (quantized
                                     to the layer's static per-tensor scale)
    w_mm: (Go, u_out, Gi, Kh, Kw, u) int8 map-major weights
    s_mm: (Go, u_out)                f32 combined dequant scale per output
                                     channel: act_scale * weight_scale[c]
    b_mm: (Go, u_out)                optional f32 bias, added after dequant

    The accumulator is int32 VMEM scratch (``preferred_element_type=int32``
    on every MXU dot, so MACs are exact); the flush multiplies by ``s_mm``,
    folds bias/ReLU, and writes ``out_dtype``.
    """
    assert x_mm.dtype == jnp.int8, x_mm.dtype
    assert w_mm.dtype == jnp.int8, w_mm.dtype
    return _conv_call(x_mm, w_mm, s_mm, b_mm, stride=stride, out_hw=out_hw,
                      operand_dtype=jnp.int8, acc_dtype=jnp.int32,
                      out_dtype=out_dtype, apply_relu=apply_relu,
                      interpret=interpret)
