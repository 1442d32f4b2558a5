"""Jit'd wrappers: Pallas on TPU (or interpret), jnp oracle elsewhere.

``solve_p1_all_fused`` is the kernel-accelerated P1 solver: the EG iteration
runs the fused eg_step kernel; the gradient (two [V,K]x[K,K] matmuls) stays
on the MXU via plain jnp."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import kernel, ref

_EPS = 1e-12


def _use_kernel(interpret: bool) -> bool:
    return interpret or jax.default_backend() == "tpu"


def kl_rows(states, target, *, interpret: bool = False):
    if _use_kernel(interpret):
        return kernel.kl_rows(states, target, interpret=interpret)
    return ref.kl_rows_ref(states, target)


def entropy_rows(states, *, interpret: bool = False):
    if _use_kernel(interpret):
        return kernel.entropy_rows(states, interpret=interpret)
    return ref.entropy_rows_ref(states)


@partial(jax.jit, static_argnames=("num_steps", "step_size", "interpret"))
@jax.named_scope("p1_solve")
def solve_p1_all_fused(states, target, contact_matrix, *, num_steps: int = 400,
                       step_size: float = 2.0, interpret: bool = False):
    """Kernel-backed drop-in for repro.core.kl_solver.solve_p1_all."""
    m = contact_matrix.astype(jnp.float32)
    n_act = jnp.maximum(jnp.sum(m, axis=1, keepdims=True), 1.0)
    alpha0 = m / n_act
    g = jnp.clip(target.astype(jnp.float32), _EPS, None)
    log_g = jnp.log(g)

    step = (partial(kernel.eg_step, step_size=step_size, interpret=interpret)
            if _use_kernel(interpret) else partial(ref.eg_step_ref, step_size=step_size))

    def body(_, alpha):
        u = jnp.clip(alpha @ states, _EPS, None)           # [V, K] mixed states
        grad = (jnp.log(u) - log_g + 1.0) @ states.T       # [V, K] dKL/dalpha
        return step(alpha, grad, m)

    return jax.lax.fori_loop(0, num_steps, body, alpha0)
