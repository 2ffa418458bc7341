"""Plain float32 forward pass of a network description.

Walks the ``NetworkDescription``'s layers with op implementations of its
own, in straightforward ``jax.numpy`` at float32 and ``highest`` matmul
precision: no plan, no kernels, no batching buckets, no compute modes.
It shares no code with the program under test; only the description
(layer kinds, shapes and attributes) and the weights are common.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _conv(layer, p, x):
    y = lax.conv_general_dilated(
        x, p["w"].astype(jnp.float32), (layer.stride, layer.stride),
        layer.padding, dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=HIGHEST)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)[None, :, None, None]
    return y


def _dense(layer, p, x):
    y = jnp.dot(x.reshape(x.shape[0], -1), p["w"].astype(jnp.float32),
                precision=HIGHEST)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y


def _pool(layer, x, init, op):
    return lax.reduce_window(x, init, op, (1, 1, layer.pool_size,
                                           layer.pool_size),
                             (1, 1, layer.stride, layer.stride),
                             layer.padding)


def _avgpool(layer, x):
    ones = jnp.ones_like(x)
    return (_pool(layer, x, 0.0, lax.add) / _pool(layer, ones, 0.0, lax.add))


def _lrn(layer, x):
    """Caffe's across-channel LRN: x / (1 + alpha/n * sum of squares over
    the n channels centred on each)^beta."""
    n = layer.lrn_size
    half = n // 2
    sq = jnp.pad(x * x, ((0, 0), (half, half), (0, 0), (0, 0)))
    window = sum(sq[:, i:i + x.shape[1]] for i in range(n))
    return x / (1.0 + layer.lrn_alpha / n * window) ** layer.lrn_beta


def _softmax(x):
    e = jnp.exp(x - x.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


OPS: Dict[str, Callable] = {
    "conv": lambda l, p, ins: _conv(l, p, ins[0]),
    "dense": lambda l, p, ins: _dense(l, p, ins[0]),
    "relu": lambda l, p, ins: jnp.maximum(ins[0], 0.0),
    "maxpool": lambda l, p, ins: _pool(l, ins[0], -jnp.inf, lax.max),
    "avgpool": lambda l, p, ins: _avgpool(l, ins[0]),
    "gap": lambda l, p, ins: ins[0].mean(axis=(2, 3)),
    "lrn": lambda l, p, ins: _lrn(l, ins[0]),
    "flatten": lambda l, p, ins: ins[0].reshape(ins[0].shape[0], -1),
    "concat": lambda l, p, ins: jnp.concatenate(ins, axis=1),
    "softmax": lambda l, p, ins: _softmax(ins[0]),
}


def forward(net, params, x):
    """The network's output for images ``x`` (B, C, H, W), in float32."""
    acts = {"input": x.astype(jnp.float32)}
    for layer in net.layers:
        ins = [acts[i] for i in layer.inputs]
        acts[layer.name] = OPS[layer.kind](layer, params.get(layer.name), ins)
    return acts[net.layers[-1].name]


def make(net, params, block: int):
    """A jitted reference over blocks of ``block`` images; returns a function
    of a host array (n, C, H, W) giving a host array (n, classes)."""
    import numpy as np

    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, x: forward(net, p, x))

    def run(images: "np.ndarray") -> "np.ndarray":
        out = []
        for i in range(0, len(images), block):
            x = images[i:i + block]
            n = len(x)
            if n < block:                     # one compiled shape only
                x = np.concatenate([x, np.zeros((block - n, *x.shape[1:]),
                                                x.dtype)])
            with jax.default_matmul_precision("highest"):
                out.append(np.asarray(fn(params, x))[:n])
        return np.concatenate(out)
    return run
