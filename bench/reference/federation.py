"""A plain DFL-DDS federation (arXiv 2209.01750, Alg. 1), written from the
paper and independent of the program: the reference that decides whether
the timed path is ``correct``.

Every epoch, on dense [K, K] matrices and one vehicle at a time where the
program stacks and gathers:

1. each vehicle draws E minibatches of B samples from its own partition;
2. P1 (Eq. 11): exponentiated-gradient steps on the simplex over the
   vehicle's contact set give its aggregation weights alpha;
3. the gossip mix (Eq. 10): every vehicle's model becomes the alpha-weighted
   sum of its contacts' models;
4. E local SGD steps per vehicle, with the model's dropout;
5. state vectors (Eqs. 5-7): S <- W S, then lr * E on the diagonal and each
   row normalised;
and on the evaluated epochs each vehicle's accuracy on the eval samples.

The random streams are drawn as the program draws them (the same key
splits, partition and mobility draws), so for one seed both compute the same
federation and differ only by rounding. ``dtype`` float32 runs at
``highest`` matmul precision; bfloat16 is the control.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import mobility

EPS = 1e-12


@dataclass(frozen=True)
class Job:
    num_vehicles: int
    epochs: int
    eval_every: int
    eval_samples: int
    local_steps: int
    batch_size: int
    lr: float
    p1_steps: int
    p1_step_size: float
    grid_side: int
    grid_spacing: float
    comm_range: float
    epoch_duration: float
    shards_per_vehicle: int = 4


def partition(labels: np.ndarray, k: int, shards_per_vehicle: int,
              seed: int) -> np.ndarray:
    """Balanced non-IID (paper Sec. VI-A.4): label-sorted samples cut into
    ``shards_per_vehicle * k`` equal shards, dealt out at random. Returns
    the [K, n_k] index table."""
    order = np.argsort(labels, kind="stable")
    n = k * shards_per_vehicle
    shards = np.split(order[: (len(order) // n) * n], n)
    deal = np.random.default_rng(seed).permutation(n)
    return np.stack([np.concatenate([shards[s] for s in
                                     deal[i * shards_per_vehicle:
                                          (i + 1) * shards_per_vehicle]])
                     for i in range(k)])


def p1_weights(states, target, member, steps: int, step_size: float):
    """One vehicle's P1: min KL(alpha^T S || g) over the simplex on
    ``member`` (0/1 over all K), by exponentiated gradient with the
    gradient centred over the members and the step capped at ``step_size``
    in log-weight."""
    m = member.astype(states.dtype)
    n = jnp.maximum(jnp.sum(m), 1.0)
    log_g = jnp.log(jnp.clip(target, EPS, None))

    def body(_, a):
        u = jnp.clip(a @ states, EPS, None)
        grad = states @ (jnp.log(u) - log_g + 1.0)
        centred = (grad - jnp.sum(grad * m) / n) * m
        scale = step_size / jnp.maximum(jnp.max(jnp.abs(centred)), 1.0)
        logits = jnp.where(m > 0, jnp.log(jnp.clip(a, EPS, 1.0)) - scale * centred,
                           -jnp.inf)
        new = jax.nn.softmax(logits) * m
        return new / jnp.maximum(jnp.sum(new), EPS)

    return jax.lax.fori_loop(0, steps, body, m / n)


def kl_bits(states, target):
    """Mean over vehicles of KL(s_k || g) in bits, 0 log 0 = 0."""
    s = jnp.clip(states, EPS, 1.0)
    g = jnp.clip(target, EPS, 1.0)
    terms = jnp.where(states > EPS, states * (jnp.log2(s) - jnp.log2(g)), 0.0)
    return jnp.mean(jnp.sum(terms, axis=-1))


def _cast(a, dtype):
    """``a`` on the device, in ``dtype`` if it is floating point; integer
    data (token ids) keeps its type."""
    a = jnp.asarray(a)
    return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a


class Federation:
    """The reference for one (model, job, dataset, seed). The model is a
    configuration module (``bench/harness.py`` names what it defines); the
    reference uses its ``init``, ``loss`` and ``accuracy``."""

    def __init__(self, model, job: Job, data, seed: int, dtype=jnp.float32,
                 block: int = 25):
        self.model, self.job, self.dtype = model, job, dtype
        k = job.num_vehicles
        self.block = min(block, k)
        table = partition(np.asarray(data.train_y), k, job.shards_per_vehicle,
                          seed)
        self.table = jnp.asarray(table, jnp.int32)
        self.target = jnp.full((k,), 1.0 / k, dtype)   # equal shards
        self.x = _cast(data.train_x, dtype)
        self.y = jnp.asarray(data.train_y)
        self.eval_x = _cast(data.test_x[: job.eval_samples], dtype)
        self.eval_y = jnp.asarray(data.test_y[: job.eval_samples])
        pos, adj = mobility.grid(job.grid_side, job.grid_spacing)
        fleet = mobility.Manhattan(pos, adj, k, job.epoch_duration, seed)
        self.contacts = mobility.contacts(fleet.positions(job.epochs),
                                          job.comm_range)
        key, kinit = jax.random.split(jax.random.PRNGKey(seed))
        self.key = key
        self.init_params = model.init(kinit)

    def _train_vehicle(self, args):
        p, xs, ys, key = args
        lr = jnp.asarray(self.job.lr, self.dtype)

        def step(p, inp):
            x, y, r = inp
            loss, g = jax.value_and_grad(self.model.loss)(p, x, y, r)
            return jax.tree_util.tree_map(lambda w, d: w - lr * d, p, g), loss

        rs = jax.random.split(key, xs.shape[0])
        p, losses = jax.lax.scan(step, p, (xs, ys, rs))
        return p, jnp.mean(losses)

    @partial(jax.jit, static_argnums=0)
    def _epoch(self, params, states, key, contacts, x, y, table, target):
        j, k = self.job, self.job.num_vehicles
        key, kb, kr = jax.random.split(key, 3)
        picks = jax.random.randint(kb, (k, j.local_steps, j.batch_size), 0,
                                   table.shape[1])
        idx = table[jnp.arange(k)[:, None, None], picks]
        contacts = contacts.astype(self.dtype)
        alpha = jax.vmap(lambda c: p1_weights(
            states, target, c, j.p1_steps, j.p1_step_size))(contacts)
        w = alpha * contacts
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), EPS)
        params = jax.tree_util.tree_map(
            lambda x: jnp.tensordot(w, x, axes=([1], [0])), params)
        params, losses = jax.lax.map(
            self._train_vehicle,
            (params, x[idx], y[idx], jax.random.split(kr, k)),
            batch_size=self.block)
        bump = jnp.asarray(j.lr, self.dtype) * j.local_steps
        states = w @ states + bump * jnp.eye(k, dtype=self.dtype)
        states = states / jnp.sum(states, axis=-1, keepdims=True)
        return params, states, key, jnp.mean(losses), kl_bits(states, target)

    @partial(jax.jit, static_argnums=0)
    def _accuracy(self, params, x, y):
        return jax.lax.map(lambda p: self.model.accuracy(p, x, y), params,
                           batch_size=self.block)

    def run(self, epochs: int | None = None) -> dict:
        """Run the first ``epochs`` epochs (all by default). Returns the
        per-epoch mean loss and mean KL, the accuracies [n_eval, K] of the
        evaluated epochs, and the first and last parameter stacks."""
        j = self.job
        epochs = j.epochs if epochs is None else epochs
        k = j.num_vehicles
        precision = "highest" if self.dtype == jnp.float32 else "default"
        with jax.default_matmul_precision(precision):
            params = jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p.astype(self.dtype),
                                           (k,) + p.shape), self.init_params)
            first = params
            states = jnp.zeros((k, k), self.dtype)
            key = self.key
            loss, kl, acc, evaluated = [], [], [], []
            for t in range(epochs):
                params, states, key, l, d = self._epoch(
                    params, states, key, jnp.asarray(self.contacts[t]),
                    self.x, self.y, self.table, self.target)
                loss.append(float(l))
                kl.append(float(d))
                if (t + 1) % j.eval_every == 0 or t == j.epochs - 1:
                    acc.append(np.asarray(self._accuracy(
                        params, self.eval_x, self.eval_y)))
                    evaluated.append(t + 1)
        return {"loss": loss, "kl": kl, "accuracy": acc, "evaluated": evaluated,
                "first": first, "last": params}
