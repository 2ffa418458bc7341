"""Operations and bytes of a network's conv and dense layers, from shapes.

Counted from the layer shapes alone, as the algorithm needs them: one
multiply-add is 2 operations; bytes are the layer's input, weights,
bias and output, each read or written once, at the width the compute
mode stores them in.  Lane padding, phase splits and layout copies are
the implementation's own cost and are left out, so a time computed from
these counts is a lower bound on any implementation's time.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple


@dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def ideal_seconds(self, peak_flops: float, peak_bytes_per_s: float) -> float:
        """The roofline's least time: the larger of compute and traffic."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_per_s)


def _window_out(h: int, k: int, stride: int, padding: str) -> int:
    if padding == "SAME":
        return -(-h // stride)
    return (h - k) // stride + 1


def shapes(net) -> Dict[str, Tuple[int, ...]]:
    """Per-layer output shape, batch excluded, walking the description."""
    out: Dict[str, Tuple[int, ...]] = {"input": tuple(net.input_shape)}
    for l in net.layers:
        ins = [out[i] for i in l.inputs]
        s = ins[0]
        if l.kind == "conv":
            out[l.name] = (l.out_channels,
                           _window_out(s[1], l.kernel, l.stride, l.padding),
                           _window_out(s[2], l.kernel, l.stride, l.padding))
        elif l.kind in ("maxpool", "avgpool"):
            out[l.name] = (s[0],
                           _window_out(s[1], l.pool_size, l.stride, l.padding),
                           _window_out(s[2], l.pool_size, l.stride, l.padding))
        elif l.kind in ("relu", "lrn", "softmax"):
            out[l.name] = s
        elif l.kind == "gap":
            out[l.name] = (s[0],)
        elif l.kind == "flatten":
            out[l.name] = (math.prod(s),)
        elif l.kind == "dense":
            out[l.name] = (l.out_channels,)
        elif l.kind == "concat":
            out[l.name] = (sum(i[0] for i in ins),) + tuple(s[1:])
        else:
            raise ValueError(f"layer {l.name}: unknown kind {l.kind!r}")
    return out


def layer_cost(net, name: str, batch: int, operand_bytes: int = 2,
               out_bytes: int = 2) -> Cost:
    """Operations and bytes of one conv or dense layer at ``batch``."""
    sh = shapes(net)
    layer = next(l for l in net.layers if l.name == name)
    cin_shape = sh[layer.inputs[0]]
    out_shape = sh[layer.name]
    if layer.kind == "conv":
        cin = cin_shape[0]
        macs_per_image = (math.prod(out_shape) * cin
                          * layer.kernel * layer.kernel)
        weights = layer.out_channels * cin * layer.kernel * layer.kernel
    elif layer.kind == "dense":
        k = math.prod(cin_shape)
        macs_per_image = k * layer.out_channels
        weights = k * layer.out_channels
    else:
        raise ValueError(f"layer {name} is a {layer.kind}, not conv or dense")
    bias = layer.out_channels if layer.use_bias else 0
    traffic = (batch * math.prod(cin_shape) * operand_bytes
               + weights * operand_bytes + bias * 4
               + batch * math.prod(out_shape) * out_bytes)
    return Cost(flops=2.0 * macs_per_image * batch, bytes=float(traffic))


def model_flops_per_image(net) -> float:
    """2 x multiply-adds of every conv and dense layer, for one image."""
    return sum(layer_cost(net, l.name, 1).flops
               for l in net.layers if l.kind in ("conv", "dense"))
