"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.gossip_mix import gossip_mix_matmul, gossip_mix_matmul_ref, mix_params_pallas
from repro.kernels.kl_simplex import (eg_step, eg_step_ref, entropy_rows_kernel,
                                      entropy_rows_ref, kl_rows_kernel, kl_rows_ref,
                                      solve_p1_all_fused)
from repro.core import kl_solver


# ----------------------------------------------------------- gossip_mix ----

@pytest.mark.parametrize("k,p,dtype", [
    (7, 33, jnp.float32), (16, 512, jnp.float32), (64, 2048, jnp.float32),
    (100, 700, jnp.float32), (12, 257, jnp.bfloat16), (8, 128, jnp.bfloat16),
])
def test_gossip_mix_sweep(k, p, dtype):
    r = np.random.default_rng(k * 1000 + p)
    w = jnp.asarray(r.dirichlet(np.ones(k), size=k), jnp.float32)
    x = jnp.asarray(r.normal(size=(k, p)), dtype)
    got = gossip_mix_matmul(w, x, interpret=True)
    ref = gossip_mix_matmul_ref(w, x)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_gossip_mix_pytree_wrapper():
    r = np.random.default_rng(0)
    k = 6
    w = jnp.asarray(r.dirichlet(np.ones(k), size=k), jnp.float32)
    tree = {"a": jnp.asarray(r.normal(size=(k, 3, 5)), jnp.float32),
            "b": jnp.asarray(r.normal(size=(k, 11)), jnp.float32)}
    from repro.core import aggregation
    got = mix_params_pallas(w, tree)  # interpret mode off the TPU
    ref = aggregation.mix_params(w, tree)
    for key in tree:
        np.testing.assert_allclose(np.asarray(got[key]), np.asarray(ref[key]), atol=1e-5)


# ------------------------------------------------------------ kl_simplex ----

@settings(max_examples=15, deadline=None)
@given(st.integers(1, 40), st.integers(2, 50), st.integers(0, 100))
def test_kl_entropy_rows_property(v, k, seed):
    r = np.random.default_rng(seed)
    s = jnp.asarray(r.dirichlet(np.ones(k), size=v), jnp.float32)
    g = jnp.asarray(r.dirichlet(np.ones(k) * 2), jnp.float32)
    np.testing.assert_allclose(np.asarray(kl_rows_kernel(s, g, interpret=True)),
                               np.asarray(kl_rows_ref(s, g)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(entropy_rows_kernel(s, interpret=True)),
                               np.asarray(entropy_rows_ref(s)), atol=1e-5)


@pytest.mark.parametrize("v,k", [(4, 8), (33, 100), (128, 16)])
def test_eg_step_matches_ref(v, k):
    r = np.random.default_rng(v * k)
    m = jnp.asarray((r.random((v, k)) < 0.5), jnp.float32).at[:, 0].set(1)
    a = jnp.asarray(r.dirichlet(np.ones(k), size=v), jnp.float32) * m
    a = a / jnp.sum(a, 1, keepdims=True)
    g = jnp.asarray(r.normal(size=(v, k)), jnp.float32)
    got = eg_step(a, g, m, interpret=True)
    ref = eg_step_ref(a, g, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_fused_solver_reaches_core_objective():
    r = np.random.default_rng(9)
    k = 20
    s = jnp.asarray(r.dirichlet(np.ones(k), size=k), jnp.float32)
    g = jnp.asarray(r.dirichlet(np.ones(k) * 2), jnp.float32)
    c = jnp.asarray(np.minimum((r.random((k, k)) < 0.3) +
                               (r.random((k, k)) < 0.3).T + np.eye(k), 1), jnp.float32)
    w_core = kl_solver.solve_p1_all(s, g, c)
    w_fused = solve_p1_all_fused(s, g, c, interpret=True)
    o_core = np.array([float(kl_solver.kl_objective(w_core[i], s, g)) for i in range(k)])
    o_fused = np.array([float(kl_solver.kl_objective(w_fused[i], s, g)) for i in range(k)])
    np.testing.assert_allclose(o_fused, o_core, atol=1e-5)


# ------------------------------------------------------- flash_attention ----

@pytest.mark.parametrize("b,s,h,kv,hd,causal,win,dtype", [
    (2, 64, 4, 4, 32, True, None, jnp.float32),
    (1, 100, 8, 2, 64, True, None, jnp.float32),
    (2, 33, 4, 1, 16, True, None, jnp.float32),
    (1, 128, 4, 4, 64, True, 32, jnp.float32),
    (1, 96, 2, 2, 128, False, None, jnp.float32),
    (2, 64, 4, 4, 64, True, None, jnp.bfloat16),
    (1, 257, 2, 1, 64, True, 100, jnp.float32),
])
def test_flash_attention_sweep(b, s, h, kv, hd, causal, win, dtype):
    r = np.random.default_rng(s * h)
    q = jnp.asarray(r.normal(size=(b, s, h, hd)), dtype)
    k = jnp.asarray(r.normal(size=(b, s, kv, hd)), dtype)
    v = jnp.asarray(r.normal(size=(b, s, kv, hd)), dtype)
    got = flash_attention(q, k, v, causal=causal, window=win,
                          interpret=True, block_q=32, block_k=32)
    ref = flash_attention_ref(q, k, v, causal=causal, window=win)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_attention_block_shape_invariance():
    r = np.random.default_rng(1)
    q = jnp.asarray(r.normal(size=(1, 70, 2, 32)), jnp.float32)
    k = jnp.asarray(r.normal(size=(1, 70, 2, 32)), jnp.float32)
    v = jnp.asarray(r.normal(size=(1, 70, 2, 32)), jnp.float32)
    o1 = flash_attention(q, k, v, interpret=True, block_q=16, block_k=64)
    o2 = flash_attention(q, k, v, interpret=True, block_q=64, block_k=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
