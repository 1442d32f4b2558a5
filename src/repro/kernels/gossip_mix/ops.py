"""Jit'd public wrapper: apply the gossip mix to a parameter pytree through
the Pallas kernels.

Both mixing representations route through here behind the
``SimulationConfig.mixing_backend = "pallas"`` knob: a dense ``[K_out,
K_in]`` matrix hits the blocked matmul kernel, a ``core.contacts
.SparseMixing`` neighbour list hits the scalar-prefetch gather kernel. The
kernel always runs: compiled on a TPU, in interpret mode elsewhere. The jnp
references in ``ref.py`` are what tests compare it against, never a
substitute for it."""
from __future__ import annotations

import jax

from ...core.contacts import SparseMixing
from .kernel import gossip_mix_gather, gossip_mix_matmul


@jax.named_scope("gossip_mix")
def mix_params_pallas(mixing, params):
    """Drop-in replacement for repro.core.aggregation.mix_params.

    Flattens every leaf to [K_in, -1], runs the blocked kernel, reshapes
    back. ``mixing`` may be rectangular [K_out, K_in] — the per-shard
    partial-matmul block of the shard_map backend — or a ``SparseMixing``
    whose ids address the leaf rows (possibly shard-remapped), in which case
    the gather kernel runs. Off the TPU the kernel runs in interpret mode.
    """
    interpret = jax.default_backend() != "tpu"
    if isinstance(mixing, SparseMixing):
        run = lambda x: gossip_mix_gather(mixing.idx, mixing.w, x,
                                          interpret=interpret)
        k_out = mixing.idx.shape[0]
    else:
        run = lambda x: gossip_mix_matmul(mixing, x, interpret=interpret)
        k_out = mixing.shape[0]

    def mix_leaf(x: jax.Array) -> jax.Array:
        flat = x.reshape(x.shape[0], -1)
        return run(flat).reshape((k_out,) + x.shape[1:])

    return jax.tree_util.tree_map(mix_leaf, params)
