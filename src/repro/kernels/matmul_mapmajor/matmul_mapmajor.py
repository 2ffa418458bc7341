"""Pallas TPU kernel: blocked matmul under Cappuccino compute modes.

The FC / 1x1-conv / transformer-projection hot path.  Map-major grouping is
the identity for a 2-D operand (the reduction dim is already minor), so the
paper's C2 contribution here reduces to MXU-aligned (multiple-of-128)
blocking; C4 (inexact modes) chooses the operand/accumulator dtypes:

  PRECISE        f32 x f32 -> f32 accum (runs below MXU peak — the paper's
                 'vector processing unavailable in precise mode')
  RELAXED        bf16 x bf16 -> f32 accum (MXU native)
  IMPRECISE      bf16 x bf16 -> bf16 accum
  IMPRECISE_INT8 int8 x int8 -> int32 accum via :func:`matmul_mapmajor_int8`
                 with the dequant(+bias+ReLU) epilogue fused into the flush
                 (uncalibrated layers dequantize to bf16 in the wrapper).

Grid (M/bm, N/bn, K/bk), K innermost, f32/bf16/int32 VMEM scratch
accumulator, output block revisited across K steps — the canonical TPU
matmul schedule.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret
from ...core.precision import ComputeMode


def _mm_kernel(a_ref, b_ref, *refs, n_k: int, out_dtype, acc_dtype,
               has_scale: bool, has_bias: bool, apply_relu: bool):
    """One grid cell of the blocked matmul.

    Optional refs (in order, per flags): s_ref (1, bn) combined dequant
    scale per output column (int8 datapath), bias_ref (1, bn).  The
    epilogue runs once, at the K-loop flush, on the VMEM accumulator —
    dequant then bias then ReLU — so a fused dense group is one launch.
    """
    refs = list(refs)
    s_ref = refs.pop(0) if has_scale else None
    bias_ref = refs.pop(0) if has_bias else None
    o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype)

    @pl.when(k == n_k - 1)
    def _flush():
        out = acc_ref[...]
        if has_scale:
            out = out.astype(jnp.float32) * s_ref[...]
        if has_bias:
            out = out + bias_ref[...].astype(out.dtype)
        if apply_relu:
            out = jnp.maximum(out, 0)
        o_ref[...] = out.astype(out_dtype)


def matmul_mapmajor(a: jnp.ndarray, b: jnp.ndarray, *,
                    mode: ComputeMode = ComputeMode.RELAXED,
                    bm: int = 256, bn: int = 256, bk: int = 512,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """(M, K) @ (K, N) under a compute mode.  Dims must divide the blocks
    (the ops.py wrapper pads)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, (bm, bn, bk))

    kernel = functools.partial(_mm_kernel, n_k=k // bk,
                               out_dtype=mode.out_dtype,
                               acc_dtype=mode.accum_dtype,
                               has_scale=False, has_bias=False,
                               apply_relu=False)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), mode.out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), mode.accum_dtype)],
        interpret=resolve_interpret(interpret),
    )(a.astype(mode.operand_dtype), b.astype(mode.operand_dtype))


def matmul_mapmajor_int8(a: jnp.ndarray, b: jnp.ndarray, s: jnp.ndarray,
                         bias: jnp.ndarray = None, *,
                         apply_relu: bool = False,
                         out_dtype=jnp.bfloat16,
                         bm: int = 256, bn: int = 256, bk: int = 512,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """The true int8 datapath for dense layers: int8 x int8 -> int32 MACs
    with the dequant(+bias+ReLU) epilogue fused into the flush.

    a: (M, K) int8 quantized activations, K a multiple of bk
    b: (K, N) int8 quantized weights, N a multiple of bn
    s: (1, N) f32 combined dequant scale per output column —
       act_scale * per-output-channel weight scale
    bias: (1, N) optional f32 bias, added after dequant

    The accumulator is int32 VMEM scratch (``preferred_element_type=int32``
    keeps the MXU MACs exact); one launch per dense(+bias+ReLU) group.
    """
    assert a.dtype == jnp.int8, a.dtype
    assert b.dtype == jnp.int8, b.dtype
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, (bm, bn, bk))
    assert s.shape == (1, n), (s.shape, n)
    has_bias = bias is not None

    kernel = functools.partial(_mm_kernel, n_k=k // bk, out_dtype=out_dtype,
                               acc_dtype=jnp.int32, has_scale=True,
                               has_bias=has_bias, apply_relu=apply_relu)
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))]
    operands = [a, b, s.astype(jnp.float32)]
    if has_bias:
        assert bias.shape == (1, n), (bias.shape, n)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias.astype(jnp.float32))

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(*operands)
