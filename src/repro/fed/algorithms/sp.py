"""SP (subgradient-push, paper baseline [5]) as a registered Algorithm."""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from ...core import baselines
from ...data import pipeline
from .base import Algorithm, AlgorithmSetup, register_algorithm

# upper bound on the materialized "full local set" batch (see SP.sample)
FULL_BATCH_CAP = 256


@register_algorithm
class SP(Algorithm):
    """Subgradient-push [5]: push-sum gossip + one full-set step per epoch.

    core.baselines.sp_round; evaluation de-biases by the push-sum weights
    (z = x / y)."""

    name = "sp"

    def init_state(self, setup: AlgorithmSetup):
        return baselines.init_push_sum(setup.params_stack, setup.total_nodes)

    def round(self, setup, state, contacts_t, target, batch, rng, fed_data):
        loss_fn = setup.loss_fn

        def grad_fn(params, b, key):
            x, y = b
            loss, grads = jax.value_and_grad(loss_fn)(params, x, y, key)
            return grads, {"loss": loss}

        return baselines.sp_round(state, contacts_t, target, batch, rng,
                                  grad_fn=grad_fn, lr=setup.cfg.lr,
                                  mix_params_fn=setup.mix_params_fn,
                                  shard=setup.shard)

    def sample(self, setup, fed_data, rng):
        # SP uses the full local dataset per iteration (paper Sec. VI-A.5);
        # cap the materialized batch at FULL_BATCH_CAP
        # resampled-from-own-partition samples — an unbiased full-batch
        # estimate that keeps single-core benchmark/campaign runs tractable
        # (at the smoke tier one SP epoch would otherwise cost ~8x a DDS
        # epoch). The cap reads the (static) index-table width at trace time
        # so it also holds under the run_seeds vmap, where tables are padded
        # to a common width.
        full_bs = min(int(fed_data.index_table.shape[-1]), FULL_BATCH_CAP)
        if setup.shard.is_sharded:
            return pipeline.sample_full_batches_sliced(
                fed_data, rng, full_bs, setup.sample_shape,
                take_rows=setup.shard.local_rows)
        return pipeline.sample_full_batches(fed_data, rng, full_bs,
                                            setup.sample_shape)

    def model_of(self, setup, state):
        return baselines.sp_model(state, shard=setup.shard)

    def state_pspec(self, setup, axis_name):
        row = P(axis_name)
        return baselines.PushSumState(
            x=jax.tree_util.tree_map(lambda _: row, setup.params_stack),
            y=P(),            # [K] push-sum weights: tiny, replicated
            state_matrix=P(),
            epoch=P(),
        )
