"""Model FLOPs counted from shapes, against the hand counts, and the peak
table."""
import json

import pytest
from tiny_bench import REPO

from bench import flops, harness


def test_forward_flops_per_sample():
    mnist = harness.load_module(REPO / "bench/configs/mnist_cnn.py")
    # MNIST: 2 x (24*24*10*25 + 8*8*20*250 + 320*50 + 50*10)
    assert mnist.forward_flops_per_sample() == 961_000


def test_parameter_counts():
    import jax

    mod = harness.load_module(REPO / "bench/configs/mnist_cnn.py")
    params = mod.init(jax.random.PRNGKey(0))
    assert sum(p.size for p in params.values()) == 21_840
    cfg = json.loads((REPO / "bench/configs/mnist_cnn.json").read_text())
    assert cfg["params"] == 21_840


def test_federation_flops_of_the_paper_cell():
    cell = harness.load_cell(REPO, "mnist_cnn.grid_k16")
    # 10 epochs of 16 x 8 x 80 samples at 3 forward passes, one eval of
    # 16 x 2000 samples: 29.52192 GFLOP an epoch of training, 30.752 an eval
    train = 16 * 8 * 80 * 3 * 961_000
    assert train == 29_521_920_000
    assert flops.federation_flops(cell) == 10 * train + 16 * 2000 * 961_000


def test_peak_by_device_kind():
    assert flops.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        flops.peak_flops("cpu")
