"""Jit'd public wrapper for the map-major OLP conv kernel.

Handles the NCHW <-> map-major boundary, SAME/VALID padding, channel-group
padding, and the VMEM envelope check with an XLA fallback.

Registers itself as the ``pallas_mapmajor`` conv implementation in the
core layer-op registry (DESIGN.md §3); the planner's first cost rule is
exactly this wrapper's :func:`fits_vmem` envelope.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.layer_ops import (add_bias, register_conv_impl,
                               register_epilogue_impl)
from ...core.layout import LANES, from_map_major, to_map_major
from ...core.plan import IMPL_PALLAS
from ...core.precision import (ComputeMode, QParams, QuantizedTensor,
                               fake_quantize_act, quantize_act_int8,
                               resolve_weight)
from ...device.profile import DEFAULT_PROFILE
from .conv_mapmajor import conv_mapmajor, conv_mapmajor_int8, conv_vmem_bytes
from .ref import pack_weights

# Scoped-VMEM budget for one grid step of the kernel (bytes); above it we
# fall back.  The number lives in the device profile (repro.device); this
# module-level name is the default-profile value, kept as the runtime
# guard's budget and as a legacy alias.  Planning against another device
# passes its profile's budget to :func:`fits_vmem` explicitly.
VMEM_INPUT_BUDGET = DEFAULT_PROFILE.vmem_budget


def _pad_amounts(h, k, s, padding):
    if padding == "SAME":
        out = -(-h // s)
    elif padding == "VALID":
        out = (h - k) // s + 1
    else:
        raise ValueError(padding)
    needed = (out - 1) * s + k
    before = (max(needed - h, 0) // 2) if padding == "SAME" else 0
    after = max(needed - h - before, 0)
    return out, before, after


def _pack_bias(b: jnp.ndarray, cout: int, u: int) -> jnp.ndarray:
    """Bias (Cout,) -> group-blocked (Go, u), lane-padded like pack_weights."""
    n_go = -(-cout // u)
    pad = n_go * u - cout
    bf = b.astype(jnp.float32)
    if pad:
        bf = jnp.pad(bf, (0, pad))
    return bf.reshape(n_go, u)


@functools.partial(jax.jit, static_argnames=("stride", "padding", "mode", "u",
                                             "interpret", "fuse_bias_relu"))
def _conv2d_mapmajor_pallas(x: jnp.ndarray, w: jnp.ndarray, b=None, *,
                            stride: int = 1, padding: str = "SAME",
                            mode: ComputeMode = ComputeMode.RELAXED,
                            u: int = LANES, interpret: Optional[bool] = None,
                            fuse_bias_relu: bool = False) -> jnp.ndarray:
    _, _, h, wdim = x.shape
    cout, _, kh, kw = w.shape
    h_out, ph0, ph1 = _pad_amounts(h, kh, stride, padding)
    w_out, pw0, pw1 = _pad_amounts(wdim, kw, stride, padding)
    xp = jnp.pad(x, ((0, 0), (0, 0), (ph0, ph1), (pw0, pw1)))

    x_mm = to_map_major(xp, u, channel_axis=1)
    w_mm = pack_weights(w, u)

    if fuse_bias_relu:
        # In-kernel epilogue: bias + ReLU on the VMEM accumulator, one
        # launch total (DESIGN.md §9).
        b_mm = _pack_bias(b, cout, u) if b is not None else None
        out_mm = conv_mapmajor(x_mm, w_mm, b_mm, stride=stride,
                               out_hw=(h_out, w_out), mode=mode,
                               apply_relu=True, interpret=interpret)
        return from_map_major(out_mm, cout, channel_axis=1)

    out_mm = conv_mapmajor(x_mm, w_mm, stride=stride, out_hw=(h_out, w_out),
                           mode=mode, interpret=interpret)
    out = from_map_major(out_mm, cout, channel_axis=1)
    if b is not None:
        out = out + b[None, :, None, None].astype(out.dtype)
    return out


@functools.partial(jax.jit, static_argnames=("stride", "padding", "u",
                                             "interpret", "fuse_bias_relu"))
def _conv2d_mapmajor_pallas_int8(x, wq, wscale, act_scale, b=None, *,
                                 stride: int = 1, padding: str = "SAME",
                                 u: int = LANES,
                                 interpret: Optional[bool] = None,
                                 fuse_bias_relu: bool = False) -> jnp.ndarray:
    """True int8 dispatch: quantize activations at the calibrated static
    scale, launch the int8 x int8 -> int32 kernel, dequant at flush.

    ``wq`` is the prepared int8 weight payload (OIHW), ``wscale`` its
    per-output-channel f32 scales, ``act_scale`` the layer's per-tensor
    activation scale (a traced f32 scalar — calibration never retraces).
    The zero padding added for SAME is exact under symmetric
    quantization (zero_point = 0 maps to int8 zero), so it is applied
    after quantization at no accuracy cost.
    """
    n, cin, h, wdim = x.shape
    cout, _, kh, kw = wq.shape
    h_out, ph0, ph1 = _pad_amounts(h, kh, stride, padding)
    w_out, pw0, pw1 = _pad_amounts(wdim, kw, stride, padding)
    xq = quantize_act_int8(x, act_scale)
    xp = jnp.pad(xq, ((0, 0), (0, 0), (ph0, ph1), (pw0, pw1)))

    x_mm = to_map_major(xp, u, channel_axis=1)
    w_mm = pack_weights(wq, u)
    # Combined dequant scale per output channel, packed (Go, u) like bias;
    # lane-padded channels get scale 0 and are sliced away below.
    s_mm = _pack_bias(wscale.reshape(-1) * act_scale, cout, u)
    b_mm = _pack_bias(b, cout, u) if b is not None else None

    out_mm = conv_mapmajor_int8(x_mm, w_mm, s_mm, b_mm, stride=stride,
                                out_hw=(h_out, w_out),
                                apply_relu=fuse_bias_relu,
                                interpret=interpret)
    return from_map_major(out_mm, cout, channel_axis=1)


def conv2d_mapmajor_int8(x: jnp.ndarray, w: QuantizedTensor, qp: QParams,
                         b=None, *, stride: int = 1, padding: str = "SAME",
                         u: int = LANES, interpret: Optional[bool] = None,
                         vmem_budget: Optional[int] = None,
                         fuse_bias_relu: bool = False) -> jnp.ndarray:
    """NCHW int8-datapath conv: int8 operands, int32 accumulation, fused
    dequant(+bias+ReLU) epilogue — one Pallas launch.

    Same VMEM envelope policy as :func:`conv2d_mapmajor` (the bf16 bound is
    used, which is conservative for 1-byte blocks); the over-budget
    fallback runs fused XLA with *fake-quantized* activations and
    dequantized weights so its numerics track the kernel path's rounding.
    """
    _, _, h, wdim = x.shape
    _, _, kh, _ = w.q.shape
    if not fits_vmem(h, wdim, kh, stride, padding, u,
                     ComputeMode.IMPRECISE_INT8, budget=vmem_budget):
        xdq = fake_quantize_act(x, qp.act_scale)
        return _conv2d_xla_fallback(
            xdq, w.dequantize(jnp.bfloat16), b, stride=stride,
            padding=padding, mode=ComputeMode.IMPRECISE_INT8,
            relu=fuse_bias_relu)
    return _conv2d_mapmajor_pallas_int8(
        x, w.q, w.scale, jnp.float32(qp.act_scale), b, stride=stride,
        padding=padding, u=u, interpret=interpret,
        fuse_bias_relu=fuse_bias_relu)


def conv2d_mapmajor(x: jnp.ndarray, w: jnp.ndarray, b=None, *,
                    stride: int = 1, padding: str = "SAME",
                    mode: ComputeMode = ComputeMode.RELAXED,
                    u: int = LANES, interpret: Optional[bool] = None,
                    vmem_budget: Optional[int] = None,
                    fuse_bias_relu: bool = False) -> jnp.ndarray:
    """NCHW in, NCHW out; map-major + Pallas OLP inside.

    x: (N, Cin, H, W); w: (Cout, Cin, Kh, Kw); optional bias (Cout,).
    ``fuse_bias_relu=True`` folds bias and ReLU into the kernel's flush
    (the fused-group epilogue): one Pallas launch computes
    ``relu(conv(x, w) + b)``.

    Enforces the kernel's VMEM envelope: when one grid step would exceed
    ``vmem_budget`` (the target device's scoped-VMEM budget; defaults to
    :data:`VMEM_INPUT_BUDGET`), the layer runs on the
    fused-XLA OLP path instead (same semantics, no VMEM ceiling).  The
    planned dispatch path passes the plan's device budget so this guard
    agrees with the planner's rule 1.  The branch is resolved on static
    shapes, so it is jit-transparent.
    """
    _, _, h, wdim = x.shape
    _, _, kh, _ = w.shape
    if not fits_vmem(h, wdim, kh, stride, padding, u, mode,
                     budget=vmem_budget):
        return _conv2d_xla_fallback(x, w, b, stride=stride, padding=padding,
                                    mode=mode, relu=fuse_bias_relu)
    return _conv2d_mapmajor_pallas(x, w, b, stride=stride, padding=padding,
                                   mode=mode, u=u, interpret=interpret,
                                   fuse_bias_relu=fuse_bias_relu)


@functools.partial(jax.jit, static_argnames=("stride", "padding", "mode",
                                             "relu"))
def _conv2d_xla_fallback(x, w, b, *, stride, padding, mode, relu=False):
    from ...core.parallelism import conv_olp
    out = conv_olp(x, w, stride=stride, padding=padding, mode=mode)
    if b is not None:
        out = out + b[None, :, None, None].astype(out.dtype)
    return jnp.maximum(out, 0) if relu else out


def fits_vmem(h: int, w: int, k: int, stride: int, padding: str, u: int,
              mode: ComputeMode, *, budget: Optional[int] = None) -> bool:
    """True iff one grid step of the kernel fits the scoped-VMEM budget.

    Counts what the step really holds (:func:`conv_vmem_bytes`: the
    double-buffered input, weight and output blocks, the accumulator and
    the per-tap temporaries) for a u-wide channel group in ``mode``'s
    dtypes — an upper bound for the int8 datapath's 1-byte operands.
    ``budget`` defaults to the default device profile's VMEM budget; the
    planner passes its target profile's budget so rule 1 is evaluated
    against the device being planned *for*, not the module default.
    """
    if budget is None:
        budget = VMEM_INPUT_BUDGET
    h_out, _, _ = _pad_amounts(h, k, stride, padding)
    w_out, _, _ = _pad_amounts(w, k, stride, padding)
    return conv_vmem_bytes(h_out=h_out, w_out=w_out, kh=k, kw=k,
                           stride=stride, u=u, u_out=u,
                           operand_dtype=mode.operand_dtype,
                           acc_dtype=mode.accum_dtype,
                           out_dtype=mode.out_dtype) <= budget


def _int8_dispatchable(plan, w) -> bool:
    """True when the true int8 datapath can run: int8 mode, prepared int8
    weights with per-*output*-channel scales, and calibrated activation
    qparams on the plan.  Anything else falls back to the dequant path."""
    return (plan.mode is ComputeMode.IMPRECISE_INT8
            and isinstance(w, QuantizedTensor)
            and plan.qparams is not None
            and w.scale.size == w.q.shape[0])


@register_conv_impl(IMPL_PALLAS)
def _conv_pallas_planned(layer, plan, params, x):
    """Registry adapter: planned map-major conv (weights resolved per mode).

    Compiles the kernel on TPU; anywhere else Pallas TPU kernels only run
    interpreted (the planner routes here off-TPU only when forced).  An
    IMPRECISE_INT8 plan carrying calibrated qparams takes the true int8
    datapath (int8 MACs, int32 accumulation, in-kernel dequant+bias).
    """
    b = params.get("b") if layer.use_bias else None
    if _int8_dispatchable(plan, params["w"]):
        return conv2d_mapmajor_int8(x, params["w"], plan.qparams, b,
                                    stride=layer.stride,
                                    padding=layer.padding, u=plan.u,
                                    vmem_budget=plan.vmem_budget)
    w = resolve_weight(params["w"], plan.mode)
    return conv2d_mapmajor(x, w, b,
                           stride=layer.stride, padding=layer.padding,
                           mode=plan.mode, u=plan.u,
                           vmem_budget=plan.vmem_budget)


@register_epilogue_impl("conv", IMPL_PALLAS)
def _conv_pallas_fused(layer, plan, params, x, epilogue):
    """Fused-epilogue hook: conv+bias+ReLU as one Pallas launch.

    ``epilogue`` is guaranteed kernel-fusible by the graph pass
    (``KERNEL_EPILOGUE_KINDS``, i.e. ReLU only) — the kernel applies it to
    the VMEM accumulator at flush time, so the fused group costs no extra
    HBM round-trip and no extra launch.  Under IMPRECISE_INT8 with
    calibrated qparams the same single launch runs int8 x int8 -> int32
    with the dequant folded into the flush epilogue, before bias+ReLU.
    """
    b = params.get("b") if layer.use_bias else None
    if _int8_dispatchable(plan, params["w"]):
        return conv2d_mapmajor_int8(x, params["w"], plan.qparams, b,
                                    stride=layer.stride,
                                    padding=layer.padding, u=plan.u,
                                    vmem_budget=plan.vmem_budget,
                                    fuse_bias_relu=True)
    w = resolve_weight(params["w"], plan.mode)
    return conv2d_mapmajor(x, w, b,
                           stride=layer.stride, padding=layer.padding,
                           mode=plan.mode, u=plan.u,
                           vmem_budget=plan.vmem_budget,
                           fuse_bias_relu=True)
