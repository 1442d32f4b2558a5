"""The paper's MNIST CNN (arXiv 2209.01750, Sec. VI-A.2) as a plain
reference: its data, loss and accuracy, and the FLOPs it needs per sample
counted from its shapes.

5x5 conv 10 / 2x2 max pool / relu / 5x5 conv 20 / pool / relu / FC 50 /
relu / dropout 0.5 / FC 10 / log-softmax; NHWC; 21,840 parameters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.layers import (conv, conv_flops, dense_flops, dropout,
                                    glorot_normal, maxpool2)

KERNELS = {"conv1_w": (5, 5, 1, 10), "conv2_w": (5, 5, 10, 20),
           "fc1_w": (320, 50), "fc2_w": (50, 10)}


def init(key) -> dict:
    ks = jax.random.split(key, 4)
    params = {}
    for k, (name, shape) in zip(ks, KERNELS.items()):
        params[name] = glorot_normal(k, shape)
        params[name.replace("_w", "_b")] = jnp.zeros((shape[-1],), jnp.float32)
    return params


def apply(p: dict, x, rng=None, train: bool = False):
    x = jax.nn.relu(maxpool2(conv(x, p["conv1_w"], p["conv1_b"], "VALID")))
    x = jax.nn.relu(maxpool2(conv(x, p["conv2_w"], p["conv2_b"], "VALID")))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1_w"] + p["fc1_b"])
    x = dropout(x, 0.5, rng, train)
    return jax.nn.log_softmax(x @ p["fc2_w"] + p["fc2_b"], axis=-1)


def loss(p: dict, x, y, rng):
    """Mean negative log-likelihood of the labels, dropout on."""
    logp = apply(p, x, rng=rng, train=True)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def accuracy(p: dict, x, y):
    pred = jnp.argmax(apply(p, x), axis=-1)
    return jnp.mean((pred == y).astype(jnp.float32))


def make_dataset(seed: int, config: dict):
    """The synthetic image set that ``config["dataset"]`` names, of the
    real sets' sizes unless ``n_train`` / ``n_test`` say otherwise."""
    from bench.data.synthetic import make_dataset as synthetic

    return synthetic(config["dataset"], seed, config.get("n_train"),
                     config.get("n_test"))


def forward_flops_per_sample() -> int:
    """961,000: conv1 24x24 outputs, conv2 8x8, then the two dense layers."""
    return (conv_flops(24, KERNELS["conv1_w"]) + conv_flops(8, KERNELS["conv2_w"])
            + dense_flops(*KERNELS["fc1_w"]) + dense_flops(*KERNELS["fc2_w"]))
