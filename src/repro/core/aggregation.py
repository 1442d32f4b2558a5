"""Model aggregation for decentralized FL: mixing matrices and the gossip mix.

One synchronized round of decentralized aggregation (Eq. 10 executed on every
vehicle) is, in stacked form,

    w_{t+1} = W_t @ w_t

with ``W_t`` the ``[K, K]`` row-stochastic matrix of aggregation weights
(supported on the time-t contact graph). On TPU this is a batched GEMM over
the vehicle axis — the TPU-native equivalent of V2V point-to-point exchange.

``mix_params`` applies W to an arbitrary parameter pytree whose leaves carry a
leading vehicle axis. The hot path can be served by the Pallas ``gossip_mix``
kernel (see repro.kernels.gossip_mix); the pure-jnp einsum below is the
reference and the default on CPU.

Every mixing constructor (and ``mix_params``) dispatches on the contact
representation: a dense ``[K, K]`` matrix yields a dense row-stochastic W,
a ``contacts.SparseContacts`` neighbour list yields a ``SparseMixing`` with
the same weights on the same edges — the sparse O(K * D_max) twin of each
dense O(K^2) path (see core/contacts.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .contacts import SparseContacts, SparseMixing, self_slots, sparse_mix_array

Array = jax.Array


def _renormalize(idx: Array, w: Array) -> SparseMixing:
    return SparseMixing(
        idx, w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-12))


@jax.named_scope("p1_solve")
def mixing_from_alpha(alpha: Array, contacts) -> Array | SparseMixing:
    """Mask + renormalize alpha rows onto the contact set -> row-stochastic W.

    Dense: ``alpha`` [K, K] against the 0/1 contact matrix. Sparse: ``alpha``
    [K, D] per-slot weights against a ``SparseContacts`` of the same layout.
    """
    if isinstance(contacts, SparseContacts):
        return _renormalize(contacts.idx, alpha * contacts.mask)
    w = alpha * contacts
    return w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-12)


def uniform_mixing(contacts) -> Array | SparseMixing:
    """W[k, k'] = 1/|P_k| on the contact set (incl. self)."""
    if isinstance(contacts, SparseContacts):
        return _renormalize(contacts.idx, contacts.mask.astype(jnp.float32))
    c = contacts.astype(jnp.float32)
    return c / jnp.maximum(jnp.sum(c, axis=-1, keepdims=True), 1e-12)


def metropolis_mixing(contacts) -> Array | SparseMixing:
    """Metropolis-Hastings weights: symmetric, doubly-stochastic on undirected
    graphs — a classic gossip baseline (beyond-paper reference point)."""
    if isinstance(contacts, SparseContacts):
        m = contacts.mask.astype(jnp.float32)
        deg = jnp.sum(m, axis=-1) - 1.0                    # exclude self
        deg_nbr = deg[contacts.idx]                        # [K, D] gather
        sel = self_slots(contacts)
        off = m * (1.0 - sel) / (1.0 + jnp.maximum(deg[:, None], deg_nbr))
        diag = 1.0 - jnp.sum(off, axis=-1)
        return SparseMixing(contacts.idx, off + sel * diag[:, None])
    c = contacts.astype(jnp.float32)
    deg = jnp.sum(c, axis=-1) - 1.0  # exclude self
    off = c * (1.0 / (1.0 + jnp.maximum(deg[:, None], deg[None, :])))
    off = off * (1.0 - jnp.eye(c.shape[0]))
    diag = 1.0 - jnp.sum(off, axis=-1)
    return off + jnp.diag(diag)


def sample_size_mixing(contacts, sample_counts: Array) -> Array | SparseMixing:
    """Decentralized-FedAvg weights [6]: proportional to neighbour sample counts."""
    counts = jnp.asarray(sample_counts, jnp.float32)
    if isinstance(contacts, SparseContacts):
        return _renormalize(contacts.idx, contacts.mask * counts[contacts.idx])
    c = contacts.astype(jnp.float32)
    w = c * counts[None, :]
    return w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-12)


@jax.named_scope("gossip_mix")
def mix_params(mixing, params):
    """Apply the gossip mix to a pytree with leading vehicle axis K.

    A ``SparseMixing`` routes through the gather + slot-scan segment sum
    (``contacts.sparse_mix_array``, O(K * D_max * P)); a dense W through the
    tensordot below.

    Every leaf ``x`` of shape ``[K, ...]`` becomes the contraction
    ``W[k, j] * x[j, ...]`` over the vehicle axis — via tensordot, NOT via a
    flatten-to-[K, P] reshape: reshaping a tensor-parallel-sharded leaf to
    [K, P] destroys its sharding and makes XLA all-gather the full weight
    before the mix (measured: +60 GB/device collective on mixtral train_4k).
    tensordot keeps the trailing dims (and their shardings) intact, so the
    only communication is the unavoidable vehicle-axis exchange of each
    device's own shard. Mixing is f32, cast back to the leaf dtype.
    """
    if isinstance(mixing, SparseMixing):
        return jax.tree_util.tree_map(lambda x: sparse_mix_array(mixing, x),
                                      params)

    def mix_leaf(x: Array) -> Array:
        mixed = jnp.tensordot(mixing.astype(jnp.float32), x.astype(jnp.float32),
                              axes=([1], [0]),
                              precision=jax.lax.Precision.HIGHEST)
        return mixed.astype(x.dtype)

    return jax.tree_util.tree_map(mix_leaf, params)


@jax.named_scope("gossip_mix")
def mix_params_lowp(mixing: Array, params):
    """Gossip mix with a bfloat16 exchange payload (beyond-paper perf
    variant): the cross-vehicle all-gather moves bf16, accumulation stays
    f32 on the MXU. Halves the gossip collective bytes at <1e-2 relative
    mixing error (weights are a convex combination, so no cancellation)."""

    def mix_leaf(x: Array) -> Array:
        mixed = jnp.tensordot(mixing.astype(jnp.bfloat16), x.astype(jnp.bfloat16),
                              axes=([1], [0]),
                              preferred_element_type=jnp.float32)
        return mixed.astype(x.dtype)

    return jax.tree_util.tree_map(mix_leaf, params)


def consensus_distance(params, axis_name: str | None = None) -> Array:
    """Xi_t^2 = (1/K) sum_k || w_bar - w_k ||^2 over a stacked pytree.

    With ``axis_name`` set, the leading vehicle axis of every leaf is a
    shard-local row block of a federation sharded over that mesh axis
    (shard_map backend): the global mean and the squared deviations are
    completed with psums over the axis. The global path (None) is untouched
    — bit-identical to the historical implementation.
    """
    leaves = jax.tree_util.tree_leaves(params)
    k = leaves[0].shape[0]
    if axis_name is None:
        total = 0.0
        for leaf in leaves:
            flat = leaf.reshape(k, -1).astype(jnp.float32)
            mean = jnp.mean(flat, axis=0, keepdims=True)
            total = total + jnp.sum((flat - mean) ** 2)
        return total / k

    k_global = k * jax.lax.psum(1, axis_name)
    total = 0.0
    for leaf in leaves:
        flat = leaf.reshape(k, -1).astype(jnp.float32)
        mean = jax.lax.psum(jnp.sum(flat, axis=0, keepdims=True),
                            axis_name) / k_global
        total = total + jnp.sum((flat - mean) ** 2)
    return jax.lax.psum(total, axis_name) / k_global
