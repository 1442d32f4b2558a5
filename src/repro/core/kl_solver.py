"""Solver for the paper's P1 (Eq. 11): per-vehicle aggregation weights.

  min_{alpha}  D_KL( sum_{k' in P_{k,t}} alpha_{k'} * s_{k'}  ||  g )
  s.t.         alpha on the probability simplex, alpha_{k'} = 0 outside P_{k,t}

P1 is convex over the simplex (KL is convex in its first argument, the mix is
linear in alpha). We solve it with *exponentiated gradient* (entropic mirror
descent) — the natural geometry for the simplex: every iterate is strictly
feasible, masked coordinates stay exactly zero, and the iteration is a few
fused elementwise ops + two small matmuls, so it vmaps cleanly over all K
vehicles and stays on-device inside jit.

The paper assumes an off-the-shelf convex solver; the substitution is
behaviour-preserving (same convex optimum — verified against scipy SLSQP in
tests/test_kl_solver.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import contacts as contacts_lib

Array = jax.Array

_EPS = 1e-12


def _kl_nats(u: Array, g: Array) -> Array:
    """KL(u || g) in nats; zero-coordinate convention."""
    uu = jnp.clip(u, _EPS, 1.0)
    gg = jnp.clip(g, _EPS, 1.0)
    return jnp.sum(jnp.where(u > _EPS, u * (jnp.log(uu) - jnp.log(gg)), 0.0), axis=-1)


def mixed_state(alpha: Array, states: Array) -> Array:
    """u = alpha^T S : the post-aggregation state vector. alpha [K], states [K, K]."""
    return alpha @ states


def kl_objective(alpha: Array, states: Array, target: Array) -> Array:
    """P1 objective in nats (argmin is identical to the bits version)."""
    return _kl_nats(mixed_state(alpha, states), target)


def _kl_grad(alpha: Array, states: Array, target: Array) -> Array:
    """Analytic gradient: d/d alpha_i = sum_j S[i,j] (log(u_j/g_j) + 1)."""
    u = jnp.clip(mixed_state(alpha, states), _EPS, None)
    g = jnp.clip(target, _EPS, None)
    return states @ (jnp.log(u) - jnp.log(g) + 1.0)


@partial(jax.jit, static_argnames=("num_steps",))
def solve_p1(
    states: Array,
    target: Array,
    contact_mask: Array,
    num_steps: int = 400,
    step_size: float = 2.0,
) -> Array:
    """Solve P1 for ONE vehicle.

    Args:
      states: ``[K, K]`` — row k' is the (already exchanged) state vector
        s_{k',t+1/2} of vehicle k'. Rows outside the contact set are ignored.
      target: ``[K]`` target vector g.
      contact_mask: ``[K]`` 0/1 — membership of P_{k,t} (must include self).
      num_steps: EG iterations.
      step_size: EG learning rate.

    Returns:
      ``[K]`` alpha, on the simplex, exactly zero off the contact set.
    """
    mask = contact_mask.astype(states.dtype)
    n_active = jnp.maximum(jnp.sum(mask), 1.0)
    alpha0 = mask / n_active

    def body(_, alpha):
        grad = _kl_grad(alpha, states, target)
        # Center the gradient over active coords: EG is invariant to constant
        # shifts, centering improves conditioning of the exponent. Normalize
        # the step by the active gradient range so one EG step never moves
        # log-weights by more than ``step_size`` — keeps large default steps
        # stable even when clipped log terms blow the gradient up.
        gbar = jnp.sum(grad * mask) / n_active
        centered = (grad - gbar) * mask
        scale = step_size / jnp.maximum(jnp.max(jnp.abs(centered)), 1.0)
        logits = jnp.where(mask > 0, jnp.log(jnp.clip(alpha, _EPS, 1.0)) - scale * centered, -jnp.inf)
        new = jax.nn.softmax(logits)
        return new * mask / jnp.maximum(jnp.sum(new * mask), _EPS)

    return jax.lax.fori_loop(0, num_steps, body, alpha0)


@partial(jax.jit, static_argnames=("num_steps",))
@jax.named_scope("p1_solve")
def solve_p1_all(
    states: Array,
    target: Array,
    contacts,
    num_steps: int = 400,
    step_size: float = 2.0,
) -> Array:
    """Solve P1 for every vehicle simultaneously (vmapped EG).

    Args:
      states: ``[K, K]`` state matrix (row k' = s_{k',t+1/2}).
      target: ``[K]``.
      contacts: ``[K, K]`` 0/1 dense matrix, row k = P_{k,t} (diag must be
        1), or a ``contacts.SparseContacts`` neighbour list.

    Returns:
      Dense contacts: ``[K, K]`` alpha rows supported on the contact set.
      Sparse contacts: ``[K, D_max]`` per-slot alpha (zero on padding) on the
      neighbour-list layout — each vehicle's EG runs over its D_max slots
      against the gathered ``[D_max, K]`` neighbour states (the same solver
      body as the dense path, so the optima agree), O(K * D_max * K) per EG
      step instead of O(K^3).
    """
    solve = partial(solve_p1, num_steps=num_steps, step_size=step_size)
    if isinstance(contacts, contacts_lib.SparseContacts):
        return _solve_p1_neighbours(states, target, contacts, solve)
    return jax.vmap(lambda m: solve(states, target, m))(contacts)


# vehicles per block of the sparse P1 solve: the vmapped EG holds the
# gathered neighbour states for a whole block — [block, D_max, K] floats —
# so blocking keeps that buffer tens of MB at K=1024 instead of the full
# [K, D_max, K] gather. Module-level so tests can shrink it to exercise the
# blocked path at tiny K.
P1_BLOCK = 256


def _solve_p1_neighbours(states, target, contacts, solve) -> Array:
    """Per-vehicle EG over the neighbour slots, in row blocks of
    ``P1_BLOCK`` vehicles (``lax.map``). Rows padding the last block solve a
    trivial one-slot P1 and are sliced off."""
    idx, mask = contacts.idx, contacts.mask
    k, d = idx.shape
    block = min(P1_BLOCK, k)
    num_blocks = -(-k // block)
    pad = num_blocks * block - k
    if pad:
        idx = jnp.concatenate([idx, jnp.zeros((pad, d), idx.dtype)], axis=0)
        mask = jnp.concatenate(
            [mask, jnp.zeros((pad, d), mask.dtype).at[:, 0].set(1)], axis=0)
    solve_rows = jax.vmap(lambda ids, m: solve(states[ids], target, m))
    if num_blocks == 1:
        return solve_rows(idx, mask)[:k]
    out = jax.lax.map(lambda b: solve_rows(*b),
                      (idx.reshape(num_blocks, block, d),
                       mask.reshape(num_blocks, block, d)))
    return out.reshape(num_blocks * block, d)[:k]
