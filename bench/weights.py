"""Seeded random weights for a network description, made on the device.

The benchmark is the model file's author: He-normal weights for every
conv and dense layer and zero biases, drawn in one jitted call from the
configuration's ``weights_seed``, in the type they are served in.  Both
the program under test and the reference are handed these same arrays.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import flops


def make(net, seed: int, dtype=jnp.bfloat16):
    """{layer: {"w": ..., "b": ...}}: conv weights OIHW, dense (K, N)."""
    sh = flops.shapes(net)
    layers = [l for l in net.layers if l.kind in ("conv", "dense")]

    def build(key):
        keys = jax.random.split(key, len(layers))
        out = {}
        for k, l in zip(keys, layers):
            cin = sh[l.inputs[0]]
            if l.kind == "conv":
                shape = (l.out_channels, cin[0], l.kernel, l.kernel)
                fan_in = cin[0] * l.kernel * l.kernel
            else:
                fan_in = math.prod(cin)
                shape = (fan_in, l.out_channels)
            w = jax.random.normal(k, shape, jnp.float32) * math.sqrt(2.0 / fan_in)
            p = {"w": w.astype(dtype)}
            if l.use_bias:
                p["b"] = jnp.zeros((l.out_channels,), dtype)
            out[l.name] = p
        return out

    return jax.block_until_ready(jax.jit(build)(jax.random.PRNGKey(seed)))
