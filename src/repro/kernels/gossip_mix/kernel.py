"""Pallas TPU kernel for the gossip mix: out = W @ X.

W: [K, K] row-stochastic mixing matrix (K = vehicles, small — padded to the
8x128 MXU tile), X: [K, P] stacked flattened model parameters (P huge).

The aggregation step is bandwidth-bound: 2*K*P bytes moved for 2*K*K*P flops
(arithmetic intensity = K flops/byte, K ~ 16-128). Tiling: W lives in VMEM
whole; X/out stream through VMEM in (K_pad, BLOCK_P) tiles; f32 accumulation
on the MXU. One grid axis over P tiles — each tile is read and written once,
which is the bandwidth optimum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

BLOCK_P = 512
LANE = 128
SUBLANE = 8


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _mix_kernel(w_ref, x_ref, o_ref):
    # w_ref: [K_pad, K_pad]; x_ref/o_ref: [K_pad, BLOCK_P] (VMEM tiles)
    w = w_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(w, x, preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_p"))
def gossip_mix_matmul(mixing: Array, flat: Array, *, interpret: bool = False,
                      block_p: int = BLOCK_P) -> Array:
    """out[k, p] = sum_j mixing[k, j] * flat[j, p], via pl.pallas_call.

    mixing: [K_out, K_in] float; flat: [K_in, P] any float dtype. Returns
    flat.dtype. K_out == K_in is the classic full gossip mix; rectangular
    blocks are the per-shard partial matmul of the shard_map backend (each
    shard multiplies the column block it owns rows for — see
    core.vehicle_axis.sharded_mix).
    """
    k_in, p = flat.shape
    k_out = mixing.shape[0]
    assert mixing.shape[1] == k_in, (mixing.shape, flat.shape)
    k_out_pad = _pad_to(max(k_out, SUBLANE), SUBLANE)
    k_in_pad = _pad_to(max(k_in, SUBLANE), SUBLANE)
    p_pad = _pad_to(max(p, LANE), block_p)

    w = jnp.zeros((k_out_pad, k_in_pad), mixing.dtype).at[:k_out, :k_in].set(mixing)
    x = jnp.zeros((k_in_pad, p_pad), flat.dtype).at[:k_in, :p].set(flat)

    out = pl.pallas_call(
        _mix_kernel,
        grid=(p_pad // block_p,),
        in_specs=[
            pl.BlockSpec((k_out_pad, k_in_pad), lambda i: (0, 0)),  # W resident
            pl.BlockSpec((k_in_pad, block_p), lambda i: (0, i)),    # X tile
        ],
        out_specs=pl.BlockSpec((k_out_pad, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k_out_pad, p_pad), flat.dtype),
        interpret=interpret,
    )(w, x)
    return out[:k_out, :p]


def _gather_mix_kernel(idx_ref, w_ref, x_ref, o_ref, *, k_out: int, d: int):
    # idx_ref/w_ref: [K_out * D] row-major, scalar-prefetched (SMEM); x_ref/
    # o_ref: [K_in_pad, BLOCK_P] / [K_out_pad, BLOCK_P] VMEM tiles. One output
    # row at a time: D scalar-indexed row loads (pl.ds with a dynamic start)
    # accumulated in f32 — the slot weights are tiny scalars, the row loads
    # stream from the resident X tile.
    def row(k, _):
        acc = jnp.zeros((1, o_ref.shape[-1]), jnp.float32)
        for slot in range(d):  # D_max is small and static: unrolled
            i = idx_ref[k * d + slot]
            wv = w_ref[k * d + slot].astype(jnp.float32)
            acc = acc + wv * x_ref[pl.ds(i, 1), :].astype(jnp.float32)
        o_ref[pl.ds(k, 1), :] = acc.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, k_out, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_p"))
def gossip_mix_gather(idx: Array, w: Array, flat: Array, *,
                      interpret: bool = False, block_p: int = BLOCK_P) -> Array:
    """Sparse gossip mix on a padded neighbour list: ``out[k, p] = sum_d
    w[k, d] * flat[idx[k, d], p]`` via pl.pallas_call.

    idx/w: [K_out, D] int32 ids + float weights (w = 0 on padding slots, so
    the clipped in-bounds padded ids contribute nothing); flat: [K_in, P].
    Arithmetic intensity matches the dense kernel's per-edge cost but only
    the D_max contacted rows are touched per output row — O(K * D_max * P)
    flops against the dense kernel's O(K^2 * P). The neighbour ids ride the
    scalar-prefetch lane (SMEM) so row loads can be dynamically indexed.
    They go in flattened to 1-D: SMEM pads a 2-D array's minor dimension to
    128 lanes, so [K, D] ids and weights would take 2 * K * 128 * 4 bytes
    and fill its 1 MiB at K = 1024.
    """
    k_in, p = flat.shape
    k_out, d = idx.shape
    assert w.shape == idx.shape, (w.shape, idx.shape)
    k_out_pad = _pad_to(max(k_out, SUBLANE), SUBLANE)
    k_in_pad = _pad_to(max(k_in, SUBLANE), SUBLANE)
    p_pad = _pad_to(max(p, LANE), block_p)

    # padded output rows gather row 0 with weight 0
    idx_pad = jnp.zeros((k_out_pad, d), jnp.int32).at[:k_out].set(
        idx).reshape(-1)
    w_pad = jnp.zeros((k_out_pad, d), jnp.float32).at[:k_out].set(
        w.astype(jnp.float32)).reshape(-1)
    x = jnp.zeros((k_in_pad, p_pad), flat.dtype).at[:k_in, :p].set(flat)

    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(p_pad // block_p,),
        in_specs=[pl.BlockSpec((k_in_pad, block_p), lambda i, *_: (0, i))],
        out_specs=pl.BlockSpec((k_out_pad, block_p), lambda i, *_: (0, i)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_mix_kernel, k_out=k_out_pad, d=d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k_out_pad, p_pad), flat.dtype),
        interpret=interpret,
    )(idx_pad, w_pad, x)
    return out[:k_out, :p]
