"""The vehicle axis as a *partitionable* dimension.

Every federation quantity in this repo is stacked on a leading vehicle axis
K: model parameters ``[K, ...]``, optimizer state, per-vehicle RNGs, batches.
The fused engine runs that axis in one of two regimes:

* **global** — the whole stack lives on one device (the vmap backend);
* **sharded** — the stack is split into ``num_shards`` contiguous row blocks
  over a named mesh axis via ``shard_map`` (the shard_map backend), with the
  small ``[K, K]`` state/contact/mixing matrices replicated on every shard.

``VehicleSharding`` captures that choice so the algorithm rounds
(``core.dfl_dds``, ``core.baselines``) are written ONCE and run in both
regimes: the round always *splits* RNGs / masks at global K (keeping the
random streams bitwise identical across backends) and then takes
``local_rows`` — the identity in the global regime, this shard's row block
under ``shard_map``.

The one cross-vehicle coupling, the gossip contraction ``W @ w`` (Eq. 10),
becomes a sharded matmul via ``sharded_mix``: each shard multiplies the
*column block* of W it owns rows of ``w`` for against its local rows — a
partial sum over its vehicles — and a tiled ``psum_scatter`` over the mesh
axis both completes the sum and deals each shard its own output rows. No
shard ever materializes the full ``[K, P]`` model stack.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from . import contacts as contacts_lib

Array = jax.Array
PyTree = Any


@dataclass(frozen=True)
class VehicleSharding:
    """How the leading vehicle axis is partitioned at trace time.

    ``axis_name`` is the mesh axis the rows are sharded over (None = the
    global single-shard regime); ``num_shards`` its size. Row blocks are
    contiguous and in mesh-axis order: shard i owns rows
    ``[i * K/num_shards, (i+1) * K/num_shards)``.
    """
    axis_name: str | None = None
    num_shards: int = 1

    @property
    def is_sharded(self) -> bool:
        return self.axis_name is not None and self.num_shards > 1

    def local_rows(self, x: Array | None) -> Array | None:
        """Slice a [K, ...] array (built at global K) to this shard's rows."""
        if x is None or not self.is_sharded:
            return x
        k_local = x.shape[0] // self.num_shards
        start = jax.lax.axis_index(self.axis_name) * k_local
        return jax.lax.dynamic_slice_in_dim(x, start, k_local, axis=0)

    def local_cols(self, w: Array) -> Array:
        """Slice a [K, K] matrix to the columns matching this shard's rows."""
        if not self.is_sharded:
            return w
        k_local = w.shape[-1] // self.num_shards
        start = jax.lax.axis_index(self.axis_name) * k_local
        return jax.lax.dynamic_slice_in_dim(w, start, k_local, axis=-1)

    def pmean(self, x: Array) -> Array:
        """Mean of a per-shard scalar/array over the vehicle mesh axis.

        Shards hold equal row counts, so the pmean of per-shard means equals
        the global mean. Identity in the single-shard regimes.
        """
        if not self.is_sharded:
            return x
        return jax.lax.pmean(x, self.axis_name)

    def psum(self, x: Array) -> Array:
        if not self.is_sharded:
            return x
        return jax.lax.psum(x, self.axis_name)


GLOBAL = VehicleSharding()


MixParamsFn = Callable[[Array, PyTree], PyTree]


def comm_buckets(leaves: list, bucket_bytes: float) -> list[list[int]]:
    """Partition pytree leaves (by index, in traversal order) into contiguous
    same-dtype buckets holding at most ``bucket_bytes`` of partial-sum
    payload each. A leaf larger than the budget gets a bucket of its own —
    leaves are never split, so the packing is a pure regrouping of the
    per-leaf collectives (BMTrain-style size bucketing)."""
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes, cur_dtype = 0, None
    for i, leaf in enumerate(leaves):
        nbytes = leaf.size * leaf.dtype.itemsize
        if cur and (leaf.dtype != cur_dtype
                    or cur_bytes + nbytes > bucket_bytes):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        cur_dtype = leaf.dtype
    if cur:
        buckets.append(cur)
    return buckets


def num_comm_buckets(payload_bytes: float, bucket_mb: float,
                     num_leaves: int) -> int:
    """Closed-form bucket count for the cost model: how many psum_scatter
    launches one gossip mix issues for ``payload_bytes`` of [K, P] partial
    sums. Per-leaf when bucketing is off; otherwise the byte-budget packing,
    which can never launch more collectives than there are leaves."""
    if bucket_mb <= 0:
        return max(1, num_leaves)
    import math

    return min(max(1, num_leaves),
               max(1, math.ceil(payload_bytes / (bucket_mb * 2**20))))


def sharded_mix(base_mix_fn: MixParamsFn, shard: VehicleSharding,
                comm_bucket_mb: float = 0.0) -> MixParamsFn:
    """Lift a global gossip-mix ``(W [K, K], pytree [K, ...]) -> [K, ...]``
    into the sharded regime: partial matmul over local vehicles + tiled
    psum_scatter over the vehicle axis (out[k] = sum_j W[k, j] x[j] with the
    j-sum distributed over shards and the k-rows dealt back out).

    ``base_mix_fn`` must accept a rectangular [K, K_local] mixing block —
    both ``aggregation.mix_params`` (tensordot) and the Pallas
    ``mix_params_pallas`` do. In the global regime the base fn is returned
    untouched, so the vmap backend's numerics are bit-identical to before.

    A ``contacts.SparseMixing`` shards the same way by *source*: the
    replicated [K, D_max] neighbour list is remapped onto this shard's local
    row block (ids outside the block are clipped in-bounds and their weights
    zeroed), the base fn's local gather produces the [K, ...] partial sums
    over the sources this shard owns, and the identical tiled psum_scatter
    completes the sum while dealing each shard its own output rows.

    ``comm_bucket_mb > 0`` turns the per-leaf scatters into a *pipelined
    bucketed* exchange: leaves are packed into ~bucket-sized [K, cols]
    payloads (``comm_buckets``) and the partial matmul for bucket i+1 is
    issued while bucket i's scatter is in flight, so XLA's async collectives
    can hide wire time behind compute. Cross-shard summation is elementwise,
    so the bucketed path is numerically identical to the per-leaf one
    (parity-tested) — only launch count and overlap change.
    """
    if not shard.is_sharded:
        return base_mix_fn

    def local_mixing(mixing, k_local: int):
        if isinstance(mixing, contacts_lib.SparseMixing):
            start = jax.lax.axis_index(shard.axis_name) * k_local
            loc = mixing.idx - start
            owned = (loc >= 0) & (loc < k_local)
            return contacts_lib.SparseMixing(
                jnp.clip(loc, 0, k_local - 1).astype(mixing.idx.dtype),
                jnp.where(owned, mixing.w, 0.0))
        return shard.local_cols(mixing)          # [K, K_local]

    def scatter(t):
        return jax.lax.psum_scatter(t, shard.axis_name, scatter_dimension=0,
                                    tiled=True)

    @jax.named_scope("gossip_mix")
    def mix(mixing, params: PyTree) -> PyTree:
        leaves, treedef = jax.tree_util.tree_flatten(params)
        mixing = local_mixing(mixing, leaves[0].shape[0])
        if comm_bucket_mb <= 0 or len(leaves) <= 1:
            partial = base_mix_fn(mixing, params)    # [K, ...] partial sums
            return jax.tree_util.tree_map(scatter, partial)
        out: list = [None] * len(leaves)
        for idxs in comm_buckets(leaves, comm_bucket_mb * 2**20):
            # partial sums for THIS bucket only — issued after the previous
            # bucket's scatter, so the runtime can overlap the two
            partial = base_mix_fn(mixing, [leaves[i] for i in idxs])
            k = partial[0].shape[0]
            flat = jnp.concatenate([p.reshape(k, -1) for p in partial], axis=1)
            dealt = scatter(flat)                    # [K_local, bucket cols]
            off = 0
            for i, p in zip(idxs, partial):
                cols = p.size // k
                out[i] = dealt[:, off:off + cols].reshape(
                    (dealt.shape[0],) + p.shape[1:])
                off += cols
        return jax.tree_util.tree_unflatten(treedef, out)

    return mix


def mixing_self_weight(mixing) -> Array:
    """The weight each vehicle keeps on itself — ``W[k, k]`` as a [K] vector
    — for one epoch's mixing in either representation. Sparse padding slots
    carry the row's own id with weight 0, so summing the self-id slots reads
    exactly the real self weight."""
    if isinstance(mixing, contacts_lib.SparseMixing):
        k = mixing.idx.shape[-2]
        rows = jnp.arange(k, dtype=mixing.idx.dtype)[:, None]
        return jnp.sum(jnp.where(mixing.idx == rows, mixing.w, 0.0), axis=-1)
    return jnp.diagonal(mixing)


def zero_self_weight(mixing):
    """The same mixing with every self weight removed: the neighbour-only
    part of the gossip contraction (``W - diag(W)``)."""
    if isinstance(mixing, contacts_lib.SparseMixing):
        k = mixing.idx.shape[-2]
        rows = jnp.arange(k, dtype=mixing.idx.dtype)[:, None]
        return contacts_lib.SparseMixing(
            mixing.idx, jnp.where(mixing.idx == rows, 0.0, mixing.w))
    return mixing * (1.0 - jnp.eye(mixing.shape[-1], dtype=mixing.dtype))


def delayed_gossip_mix(mix_fn: MixParamsFn, shard: VehicleSharding) -> Callable:
    """Double-buffered delayed gossip (``SimulationConfig.overlap =
    "delayed"``): the exchange for round t is launched concurrently with
    round t's local training, so neighbours' contributions arrive one round
    stale while each vehicle's own contribution stays current:

        out_k = sum_{j != k} W[k, j] * stale_j  +  W[k, k] * current_k

    ``mix_fn`` is the (possibly shard-wrapped) synchronous mix, applied to
    the neighbour-only mixing ``zero_self_weight(W)`` over the stale buffer;
    the self term multiplies in elementwise. With no live contacts (W = I)
    the neighbour term is exactly zero and the self weight exactly one, so
    the degenerate trajectory is bit-identical to synchronous gossip — the
    parity anchor tests/test_backends.py holds it to."""

    @jax.named_scope("gossip_mix")
    def mix(mixing, params: PyTree, stale: PyTree) -> PyTree:
        neighbours = mix_fn(zero_self_weight(mixing), stale)
        self_w = shard.local_rows(mixing_self_weight(mixing))

        def combine(n, c):
            d = self_w.reshape(self_w.shape + (1,) * (c.ndim - 1))
            return (n.astype(jnp.float32)
                    + d.astype(jnp.float32) * c.astype(jnp.float32)
                    ).astype(c.dtype)

        return jax.tree_util.tree_map(combine, neighbours, params)

    return mix


def psum_scatter_bytes(total_rows: int, row_bytes: int, num_shards: int) -> float:
    """Per-device wire bytes of one tiled ``psum_scatter`` completing the
    sharded gossip contraction: each device ships its ``[K, ...]`` partial
    sums minus the block it keeps — ``(n - 1) / n`` of ``K * row_bytes``.
    The closed-form collective-volume term of the analytical cost model
    (roofline.scenario_cost); zero in the single-shard regime."""
    if num_shards <= 1:
        return 0.0
    return (num_shards - 1) / num_shards * total_rows * row_bytes


def local_nodes(total_nodes: int, shard: VehicleSharding) -> int:
    """Rows of the vehicle axis this shard owns (static)."""
    if total_nodes % shard.num_shards:
        raise ValueError(
            f"total_nodes={total_nodes} not divisible by "
            f"num_shards={shard.num_shards}")
    return total_nodes // shard.num_shards
