"""Batching pipeline for federated training.

Everything stays on-device: the full train set lives as a device array; each
global epoch the pipeline draws per-vehicle (E local steps x B) sample
indices from the vehicle's partition (dense [K, W] index table with true
counts, see partition.pad_to_uniform) and gathers inside jit.

``FederatedData.x`` is stored flat, one row of ``H*W*C`` values a sample:
the gather copies whole rows and only the small batch is reshaped to
``[..., H, W, C]``, because a 4-D image array laid out sample-minor (what a
conv over a 1- or 3-wide channel axis asks for) makes the gather copy single
lanes. A TPU's default layout for the flat ``[N, H*W*C]`` set also puts N
minor (it pads least); the compiler then relays it out row-major once a
program, outside the epoch loop, and gathers rows from that. The samplers
take the sample shape ``(H, W, C)`` as a static argument to restore it.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class FederatedData(NamedTuple):
    x: Array            # [N, H*W*C] full train inputs, one row a sample (device)
    y: Array            # [N] labels
    index_table: Array  # [K, W] per-vehicle sample indices (padded, resampled)
    counts: Array       # [K] true per-vehicle sample counts


def make_federated_data(train_x: np.ndarray, train_y: np.ndarray,
                        dense_indices: np.ndarray, counts: np.ndarray) -> FederatedData:
    return FederatedData(
        x=jnp.asarray(train_x.reshape(len(train_x), -1)),
        y=jnp.asarray(train_y),
        index_table=jnp.asarray(dense_indices),
        counts=jnp.asarray(counts),
    )


def _gather(data: FederatedData, idx: Array, sample_shape: tuple[int, ...]):
    """Rows ``idx`` of the train set, each reshaped to ``sample_shape``."""
    return data.x[idx].reshape(idx.shape + tuple(sample_shape)), data.y[idx]


@partial(jax.jit, static_argnames=("local_steps", "batch_size", "sample_shape"))
def sample_batches(data: FederatedData, rng: Array, local_steps: int,
                   batch_size: int, sample_shape: tuple[int, ...]):
    """Draw per-vehicle minibatches: returns (x, y) of shape
    [K, E, B, *sample_shape] and [K, E, B]."""
    return sample_batches_sliced(data, rng, local_steps, batch_size,
                                 sample_shape)


@jax.named_scope("sample_batches")
def sample_batches_sliced(data: FederatedData, rng: Array, local_steps: int,
                          batch_size: int, sample_shape: tuple[int, ...],
                          take_rows=None):
    """``sample_batches`` with an optional vehicle-row slice.

    ``take_rows`` maps a [K, ...] array to the caller's rows — identity (None)
    on the single-device path, a shard-local row slice under the shard_map
    backend. The FULL [K, E, B] pick tensor is always drawn before slicing,
    so every backend consumes the identical random stream and per-vehicle
    batches match across them; only the gather is per-shard.
    """
    k, w = data.index_table.shape
    picks = jax.random.randint(rng, (k, local_steps, batch_size), 0, w)
    table = data.index_table
    if take_rows is not None:
        picks, table = take_rows(picks), take_rows(table)
    rows = jnp.arange(table.shape[0])
    idx = table[rows[:, None, None], picks]  # [K_rows, E, B]
    return _gather(data, idx, sample_shape)


def stack_federated_data(datas: list[FederatedData], seed: int = 0) -> FederatedData:
    """Stack per-seed FederatedData along a leading seed axis for the fused
    engine's ``run_seeds`` vmap.

    The train tensors must be shared across seeds (one dataset, many
    partitions) and are NOT stacked — vmap broadcasts them (in_axes None).
    Index tables may have different widths (unbalanced partitions); short
    tables are padded to the common width by resampling each row's own
    entries, the same distribution-preserving trick as partition
    ``pad_to_uniform``.
    """
    x, y = datas[0].x, datas[0].y
    # catch per-seed datasets early: broadcasting datas[0].x across seeds is
    # only sound when every seed partitioned the SAME train tensors (identity
    # check is too strict — each context converts numpy -> device anew)
    y_host = np.asarray(y)
    if any(d.x.shape != x.shape or not np.array_equal(np.asarray(d.y), y_host)
           for d in datas[1:]):
        raise ValueError("stack_federated_data requires one dataset shared "
                         "across seeds (per-seed train tensors differ)")
    width = max(int(d.index_table.shape[1]) for d in datas)
    rng = np.random.default_rng(seed)
    tables = []
    for d in datas:
        table = np.asarray(d.index_table)
        if table.shape[1] < width:
            picks = rng.integers(0, table.shape[1],
                                 size=(table.shape[0], width - table.shape[1]))
            table = np.concatenate(
                [table, np.take_along_axis(table, picks, axis=1)], axis=1)
        tables.append(table)
    return FederatedData(
        x=x, y=y,
        index_table=jnp.asarray(np.stack(tables)),
        counts=jnp.stack([d.counts for d in datas]),
    )


@partial(jax.jit, static_argnames=("batch_size", "sample_shape"))
def sample_full_batches(data: FederatedData, rng: Array, batch_size: int,
                        sample_shape: tuple[int, ...]):
    """One batch per vehicle of ``batch_size`` samples drawn from its
    partition — used by SP's single full-set local iteration (the paper's SP
    uses all local samples; we draw ``batch_size`` >= typical partition size,
    with self-resampling padding preserving the distribution)."""
    return sample_full_batches_sliced(data, rng, batch_size, sample_shape)


@jax.named_scope("sample_batches")
def sample_full_batches_sliced(data: FederatedData, rng: Array,
                               batch_size: int, sample_shape: tuple[int, ...],
                               take_rows=None):
    """``sample_full_batches`` with an optional vehicle-row slice (see
    ``sample_batches_sliced`` — full pick tensor first, slice after, so the
    random stream is backend-invariant)."""
    k, w = data.index_table.shape
    picks = jax.random.randint(rng, (k, batch_size), 0, w)
    table = data.index_table
    if take_rows is not None:
        picks, table = take_rows(picks), take_rows(table)
    idx = jnp.take_along_axis(table, picks, axis=-1)
    return _gather(data, idx, sample_shape)
