"""Plain layers for the configurations' reference models: a direct
convolution (not the program's patches-and-matmul), 2x2 max pooling, the
paper's dropout, glorot-normal initialisation, and the FLOPs each layer
needs, counted from its shapes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def conv(x, w, b, padding: str):
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b.astype(x.dtype)


def maxpool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def dropout(x, rate: float, rng, train: bool):
    """Inverted dropout; the keep mask is ``bernoulli(rng, 1 - rate)`` over
    ``x``'s shape, as the program draws it."""
    if not train:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def glorot_normal(key, shape):
    fan_in, fan_out = int(np.prod(shape[:-1])), int(shape[-1])
    return jnp.sqrt(2.0 / (fan_in + fan_out)) * jax.random.normal(
        key, shape, jnp.float32)


def conv_flops(out_hw: int, kernel: tuple[int, int, int, int]) -> int:
    """Multiply-adds x 2 of a stride-1 convolution with ``out_hw`` x
    ``out_hw`` outputs and an HWIO ``kernel``."""
    kh, kw, cin, cout = kernel
    return 2 * out_hw * out_hw * cout * kh * kw * cin


def dense_flops(n_in: int, n_out: int) -> int:
    return 2 * n_in * n_out
