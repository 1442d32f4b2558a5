"""State vectors: per-vehicle record of each data source's contribution weight.

Implements Eqs. (5)-(7) of the paper:

  Eq. (5): s^k_{k,t+1/2} = s^k_{k,t} + eta_t           (once per local iteration)
  Eq. (6): normalize the state vector to the simplex
  Eq. (7): s_{k,t+1} = sum_{k' in P_{k,t}} alpha^k_{k',t} s_{k',t+1/2}

All functions are batched over the vehicle axis (leading dim K) so the whole
federation's state lives in one ``[K, K]`` matrix ``S`` with ``S[k, k']`` the
contribution weight of source ``k'`` to vehicle ``k``'s model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import contacts as contacts_lib

Array = jax.Array


def init_state(num_vehicles: int, dtype=jnp.float32) -> Array:
    """All-zero state matrix ``[K, K]`` (paper: 'Initially, all values in a
    state vector are assigned with 0')."""
    return jnp.zeros((num_vehicles, num_vehicles), dtype=dtype)


@jax.named_scope("state_vector")
def local_update(state: Array, lr: float | Array, local_steps: int | Array,
                 update_mask: Array | None = None) -> Array:
    """Eq. (5) applied ``local_steps`` times followed by Eq. (6).

    Each vehicle k adds ``lr`` to its own coordinate once per local iteration,
    then renormalizes. Batched: adds ``local_steps * lr`` to the diagonal.

    ``update_mask`` [K] restricts the bump to participants that actually run
    local iterations — RSUs (paper Sec. V-C) hold no data and must not
    increase their own contribution weight.
    """
    k = state.shape[0]
    bump = jnp.asarray(lr, state.dtype) * jnp.asarray(local_steps, state.dtype)
    diag = jnp.eye(k, dtype=state.dtype)
    if update_mask is not None:
        diag = diag * update_mask.astype(state.dtype)[:, None]
    state = state + bump * diag
    return normalize(state)


def normalize(state: Array, eps: float = 1e-12) -> Array:
    """Eq. (6): row-normalize onto the simplex (rows that are all-zero stay zero)."""
    tot = jnp.sum(state, axis=-1, keepdims=True)
    return jnp.where(tot > eps, state / jnp.maximum(tot, eps), state)


@jax.named_scope("state_vector")
def aggregate(state: Array, mixing) -> Array:
    """Eq. (7) for all vehicles at once: ``S' = W @ S``.

    ``mixing[k, k']`` is alpha^k_{k'} (zero outside the contact set), each row
    summing to one, so every row of the result is the convex combination of the
    neighbours' state vectors. A ``contacts.SparseMixing`` applies the same
    combination as a neighbour gather + slot sum (O(K * D_max * K), no
    [K, K] @ [K, K] matmul).
    """
    if isinstance(mixing, contacts_lib.SparseMixing):
        return contacts_lib.sparse_mix_array(mixing, state)
    return mixing @ state


@jax.named_scope("state_vector")
def entropy(state: Array, eps: float = 1e-12) -> Array:
    """Eq. (8): per-vehicle entropy H(s_k) in bits. ``state`` rows must be on
    the simplex. Returns ``[K]``."""
    p = jnp.clip(state, eps, 1.0)
    h = -jnp.sum(jnp.where(state > eps, state * jnp.log2(p), 0.0), axis=-1)
    return h


@jax.named_scope("state_vector")
def kl_to_target(state: Array, target: Array, eps: float = 1e-12) -> Array:
    """Eq. (9): per-vehicle D_KL(s_k || g) in bits. Returns ``[K]``.

    Coordinates where s=0 contribute 0 (standard KL convention).
    """
    s = jnp.clip(state, eps, 1.0)
    g = jnp.clip(target, eps, 1.0)
    terms = jnp.where(state > eps, state * (jnp.log2(s) - jnp.log2(g)[None, :]), 0.0)
    return jnp.sum(terms, axis=-1)


def target_state(sample_counts: Array) -> Array:
    """The target vector g = (n_1/n, ..., n_K/n)."""
    n = jnp.asarray(sample_counts, jnp.float32)
    return n / jnp.sum(n)
