"""Partitioners + synthetic datasets + the batching pipeline."""
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.data import pipeline, synthetic
from repro.fed import partition as plib


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 500), st.integers(4, 20))
def test_balanced_noniid_properties(seed, k):
    r = np.random.default_rng(seed)
    labels = r.integers(0, 10, size=40 * k)
    parts = plib.balanced_noniid(labels, k, seed=seed)
    sizes = {len(p) for p in parts}
    assert len(sizes) == 1                      # balanced
    all_idx = np.concatenate(parts)
    assert len(np.unique(all_idx)) == len(all_idx)  # disjoint
    # non-IID: label-sorted shards give each vehicle few labels. A shard can
    # straddle one class boundary, so the bound is 2 labels per shard (the
    # paper's 2..4-labels figure assumes class sizes divisible by the shard
    # size, which real MNIST satisfies — see test below).
    for p in parts:
        assert len(np.unique(labels[p])) <= 8


def test_balanced_noniid_paper_regime():
    """Aligned class sizes (as in real MNIST): 2..4 labels per vehicle."""
    k = 10
    labels = np.repeat(np.arange(10), 4 * k)  # class size 40 == 4 shards of 10
    parts = plib.balanced_noniid(labels, k, seed=0)
    for p in parts:
        assert 1 <= len(np.unique(labels[p])) <= 4


def test_unbalanced_iid_sizes():
    parts = plib.unbalanced_iid(60_000, 30, size_choices=(150, 450, 1350), seed=0)
    for p in parts:
        assert len(p) in (150, 450, 1350)


def test_pad_to_uniform_preserves_membership():
    parts = [np.array([1, 2, 3]), np.array([10, 11, 12, 13, 14])]
    dense, counts = plib.pad_to_uniform(parts, seed=0)
    assert dense.shape == (2, 5)
    assert counts.tolist() == [3, 5]
    assert set(dense[0]) <= {1, 2, 3}           # padding resamples own indices
    assert set(dense[1]) == {10, 11, 12, 13, 14}


def test_label_histogram():
    labels = np.array([0, 0, 1, 2, 2, 2])
    h = plib.label_histogram(labels, [np.array([0, 1, 2]), np.array([3, 4, 5])], 3)
    np.testing.assert_array_equal(h, [[2, 1, 0], [0, 0, 3]])


def test_synthetic_dataset_shapes_and_learnability():
    ds = synthetic.synthetic_mnist(n_train=512, n_test=128)
    assert ds.train_x.shape == (512, 28, 28, 1)
    assert ds.test_x.shape == (128, 28, 28, 1)
    assert ds.train_x.min() >= 0 and ds.train_x.max() <= 1
    # classes must be separable: nearest-prototype in pixel space beats chance
    protos = np.stack([ds.train_x[ds.train_y == c].mean(0) for c in range(10)])
    d = ((ds.test_x[:, None] - protos[None]) ** 2).sum(axis=(2, 3, 4))
    acc = (d.argmin(1) == ds.test_y).mean()
    assert acc > 0.5, acc


def test_pipeline_batches_come_from_own_partition():
    ds = synthetic.synthetic_mnist(n_train=400, n_test=10)
    parts = plib.balanced_noniid(ds.train_y, 4, seed=0)
    dense, counts = plib.pad_to_uniform(parts)
    fd = pipeline.make_federated_data(ds.train_x, ds.train_y, dense, counts)
    xs, ys = pipeline.sample_batches(fd, jax.random.PRNGKey(0), 3, 8,
                                     ds.train_x.shape[1:])
    assert xs.shape == (4, 3, 8, 28, 28, 1)
    # every sampled label must exist in the vehicle's own partition
    for k in range(4):
        own = set(np.asarray(ds.train_y[parts[k]]))
        assert set(np.asarray(ys[k]).ravel()) <= own


def _rows_1_to_3(a):
    return a[1:3]


@pytest.mark.parametrize("full", [False, True], ids=["batches", "full"])
@pytest.mark.parametrize("take_rows", [None, _rows_1_to_3],
                         ids=["all_rows", "row_block"])
@pytest.mark.parametrize("sample_shape", [(28, 28, 1), (32, 32, 3)],
                         ids=["mnist", "cifar10"])
def test_samplers_match_image_gather_bitwise(sample_shape, take_rows, full):
    """The row gather from the flat [N, H*W*C] set gives, bit for bit and in
    the same shape, the batches of a gather from the [N, H, W, C] images
    with the same key, picks and vehicle rows."""
    n, k, w, e, b = 200, 4, 30, 3, 8
    rng = np.random.default_rng(0)
    x4d = rng.standard_normal((n,) + sample_shape).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    table = rng.integers(0, n, (k, w)).astype(np.int32)
    fd = pipeline.make_federated_data(x4d, y, table, np.full(k, w))
    assert fd.x.shape == (n, int(np.prod(sample_shape)))
    key = jax.random.PRNGKey(7)

    picks_shape = (k, b) if full else (k, e, b)
    picks = jax.random.randint(key, picks_shape, 0, w)
    ref_table = jnp.asarray(table)
    if take_rows is not None:
        picks, ref_table = take_rows(picks), take_rows(ref_table)
    if full:
        idx = jnp.take_along_axis(ref_table, picks, axis=-1)
        xs, ys = pipeline.sample_full_batches_sliced(
            fd, key, b, sample_shape, take_rows=take_rows)
    else:
        rows = jnp.arange(ref_table.shape[0])
        idx = ref_table[rows[:, None, None], picks]
        xs, ys = pipeline.sample_batches_sliced(
            fd, key, e, b, sample_shape, take_rows=take_rows)
    want_x, want_y = jnp.asarray(x4d)[idx], jnp.asarray(y)[idx]
    assert xs.shape == want_x.shape == idx.shape + sample_shape
    assert xs.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(xs), np.asarray(want_x))
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(want_y))
