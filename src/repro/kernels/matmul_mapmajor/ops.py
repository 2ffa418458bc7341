"""Jit'd wrapper: padding to block multiples + int8 weight handling.

Registers itself as the ``pallas_mapmajor`` dense implementation in the
core layer-op registry (DESIGN.md §3), including the fused-epilogue hook so
a dense+bias+ReLU group is a single launch — and, under IMPRECISE_INT8 with
calibrated qparams, a single *int8* launch (int8 x int8 -> int32 with the
dequant folded into the flush epilogue).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.layer_ops import (add_bias, register_dense_impl,
                               register_epilogue_impl)
from ...core.plan import IMPL_PALLAS
from ...core.precision import (ComputeMode, QParams, QuantizedTensor,
                               fake_quantize_act, quantize_act_int8)
from .matmul_mapmajor import matmul_mapmajor, matmul_mapmajor_int8


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("mode", "bm", "bn", "bk",
                                             "interpret"))
def _matmul_padded(a, b, mode, bm, bn, bk, interpret):
    m, n = a.shape[0], b.shape[1]
    ap = _pad_to(a, bm, bk)
    bp = _pad_to(b, bk, bn)
    out = matmul_mapmajor(ap, bp, mode=mode, bm=bm, bn=bn, bk=bk,
                          interpret=interpret)
    return out[:m, :n]


def matmul(a, w, *, mode: ComputeMode = ComputeMode.RELAXED,
           bm: int = 256, bn: int = 256, bk: int = 512,
           interpret: Optional[bool] = None):
    """(..., K) @ (K, N) with per-mode arithmetic; int8 weights dequantized
    at synthesis-prepared scale (the IMPRECISE_INT8 fallback when no
    activation qparams are available — see :func:`matmul_int8`)."""
    if isinstance(w, QuantizedTensor):
        w = w.dequantize(mode.operand_dtype)
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    out = _matmul_padded(a2, w, mode, bm, bn, bk, interpret)
    return out.reshape(*lead, w.shape[1])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "relu"))
def _matmul_padded_int8(a, wq, wscale, act_scale, b, bm, bn, bk, interpret,
                        relu):
    m, n = a.shape[0], wq.shape[1]
    aq = quantize_act_int8(a, act_scale)
    ap = _pad_to(aq, bm, bk)
    wp = _pad_to(wq, bk, bn)
    pad_n = (-n) % bn
    s = (wscale.reshape(-1) * act_scale).astype(jnp.float32)
    s = jnp.pad(s, (0, pad_n)).reshape(1, -1)
    bias = None
    if b is not None:
        bias = jnp.pad(b.astype(jnp.float32), (0, pad_n)).reshape(1, -1)
    out = matmul_mapmajor_int8(ap, wp, s, bias, apply_relu=relu,
                               bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


def matmul_int8(a, w: QuantizedTensor, qp: QParams, b=None, *,
                relu: bool = False, bm: int = 256, bn: int = 256,
                bk: int = 512, interpret: Optional[bool] = None):
    """(..., K) @ int8 (K, N) on the true int8 datapath: activations
    quantized to the calibrated static scale, int8 x int8 -> int32 MACs,
    fused dequant(+bias+ReLU) at flush — one launch for the whole group.

    Requires per-*output*-channel weight scales (axis 1 of the (K, N)
    weight, one scale per column); anything else falls back to the dequant
    path with fake-quantized activations so accuracy still tracks int8.
    """
    lead = a.shape[:-1]
    a2 = a.reshape(-1, a.shape[-1])
    n = w.q.shape[1]
    if w.scale.size != n:
        y = matmul(fake_quantize_act(a2, qp.act_scale), w,
                   mode=ComputeMode.IMPRECISE_INT8, bm=bm, bn=bn, bk=bk,
                   interpret=interpret)
        if b is not None:
            y = y + b.astype(y.dtype)
        if relu:
            y = jnp.maximum(y, 0)
        return y.reshape(*lead, n)
    out = _matmul_padded_int8(a2, w.q, w.scale,
                              jnp.float32(qp.act_scale), b,
                              bm, bn, bk, interpret, relu)
    return out.reshape(*lead, n)


def _int8_dispatchable(plan, w) -> bool:
    """True when the true int8 dense datapath can run: int8 mode, prepared
    int8 weights with per-output-channel (column) scales, and calibrated
    activation qparams on the plan."""
    return (plan.mode is ComputeMode.IMPRECISE_INT8
            and isinstance(w, QuantizedTensor)
            and plan.qparams is not None
            and w.scale.size == w.q.shape[1])


@register_dense_impl(IMPL_PALLAS)
def _dense_pallas_planned(layer, plan, params, x):
    """Registry adapter: planned map-major matmul.

    The plan's channel-group width ``u`` scales the K blocking — larger
    groups amortize more operand loads per access (paper Eq. (2)), smaller
    ones avoid padding waste on narrow layers.  An IMPRECISE_INT8 plan
    carrying calibrated qparams takes the true int8 datapath with the bias
    folded into the kernel epilogue.
    """
    bk = max(128, min(512, 4 * plan.u))
    x2 = x.reshape(x.shape[0], -1)
    if _int8_dispatchable(plan, params["w"]):
        return matmul_int8(x2, params["w"], plan.qparams,
                           params.get("b") if layer.use_bias else None,
                           bk=bk)
    y = matmul(x2, params["w"], mode=plan.mode, bk=bk)
    return add_bias(y, layer, params)


@register_epilogue_impl("dense", IMPL_PALLAS)
def _dense_pallas_fused(layer, plan, params, x, epilogue):
    """Fused-epilogue hook: dense+bias+ReLU as one Pallas launch.

    ``epilogue`` is guaranteed kernel-fusible by the graph pass (ReLU only);
    the kernel applies bias+ReLU to the VMEM accumulator at flush.  Under
    IMPRECISE_INT8 with calibrated qparams the same single launch runs
    int8 x int8 -> int32 with dequant folded in before bias+ReLU.
    """
    bk = max(128, min(512, 4 * plan.u))
    x2 = x.reshape(x.shape[0], -1)
    b = params.get("b") if layer.use_bias else None
    if _int8_dispatchable(plan, params["w"]):
        return matmul_int8(x2, params["w"], plan.qparams, b, relu=True,
                           bk=bk)
    y = add_bias(matmul(x2, params["w"], mode=plan.mode, bk=bk),
                 layer, params)
    return jnp.maximum(y, 0)
