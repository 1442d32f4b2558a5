"""Synthetic MNIST / CIFAR-10 of the real shapes, made on the device.

The recipe is the program's ``repro.data.synthetic`` generator, copied here so
that no change to the program can change the traffic: ten smooth class
prototypes (a low-resolution gaussian grid, bilinearly upsampled), and per
sample a cyclic shift of its class prototype by up to ``shift`` pixels, a
contrast gain and gaussian noise, squashed to (0, 1) by a sigmoid. The draws
come from ``jax.random`` instead of numpy, in one jitted call per dataset, so
the 60,000-image set costs milliseconds of set-up instead of seconds.

Images are [N, H, W, C] float32; labels [N] int32; 10 classes. The same
``seed`` gives the same arrays.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class Dataset:
    """The fields ``repro.fed.engine.build_context`` reads from a dataset."""
    train_x: jax.Array     # [N, H, W, C] float32, on the device
    train_y: np.ndarray    # [N] int32, on the host (the partition reads it)
    test_x: jax.Array
    test_y: jax.Array
    num_classes: int
    name: str


# name -> (H, W, C, prototype grid, shift, noise, contrast, n_train, n_test)
RECIPES = {
    "mnist": (28, 28, 1, 7, 3, 0.35, 0.25, 60_000, 10_000),
    "cifar10": (32, 32, 3, 6, 4, 0.6, 0.4, 50_000, 10_000),
}


def _prototypes(key, h: int, w: int, c: int, base: int) -> jax.Array:
    lo = jax.random.normal(key, (10, base, base, c), jnp.float32)
    ys = np.linspace(0, base - 1, h)
    xs = np.linspace(0, base - 1, w)
    y0 = np.floor(ys).astype(int); y1 = np.minimum(y0 + 1, base - 1)
    x0 = np.floor(xs).astype(int); x1 = np.minimum(x0 + 1, base - 1)
    wy = jnp.asarray(ys - y0, jnp.float32)[None, :, None, None]
    wx = jnp.asarray(xs - x0, jnp.float32)[None, None, :, None]
    return (lo[:, y0][:, :, x0] * (1 - wy) * (1 - wx)
            + lo[:, y0][:, :, x1] * (1 - wy) * wx
            + lo[:, y1][:, :, x0] * wy * (1 - wx)
            + lo[:, y1][:, :, x1] * wy * wx)


def _render(key, protos, n: int, shift: int, noise: float, contrast: float):
    k_lab, k_dy, k_dx, k_gain, k_noise = jax.random.split(key, 5)
    _, h, w, c = protos.shape
    labels = jax.random.randint(k_lab, (n,), 0, 10, jnp.int32)
    dy = jax.random.randint(k_dy, (n,), -shift, shift + 1)
    dx = jax.random.randint(k_dx, (n,), -shift, shift + 1)
    gain = 1.0 + contrast * jax.random.normal(k_gain, (n, 1, 1, 1), jnp.float32)
    rows = (jnp.arange(h)[None, :] - dy[:, None]) % h
    cols = (jnp.arange(w)[None, :] - dx[:, None]) % w
    img = protos[labels[:, None, None], rows[:, :, None], cols[:, None, :]]
    img = img * gain + noise * jax.random.normal(k_noise, img.shape, jnp.float32)
    return jax.nn.sigmoid(img), labels


@partial(jax.jit, static_argnames=("name", "n_train", "n_test"))
def _generate(key, name: str, n_train: int, n_test: int):
    h, w, c, base, shift, noise, contrast, _, _ = RECIPES[name]
    k_proto, k_train, k_test = jax.random.split(key, 3)
    protos = _prototypes(k_proto, h, w, c, base)
    train = _render(k_train, protos, n_train, shift, noise, contrast)
    test = _render(k_test, protos, n_test, shift, noise, contrast)
    return train, test


def make_dataset(name: str, seed: int, n_train: int | None = None,
                 n_test: int | None = None) -> Dataset:
    """The dataset ``name`` from ``seed``; sizes default to the real sets'."""
    n_train = n_train or RECIPES[name][7]
    n_test = n_test or RECIPES[name][8]
    (tx, ty), (vx, vy) = _generate(jax.random.PRNGKey(seed), name, n_train,
                                   n_test)
    return Dataset(tx, np.asarray(ty), vx, vy, 10, f"synthetic-{name}")
