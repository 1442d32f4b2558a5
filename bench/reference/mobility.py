"""The reference's contact graphs: a grid road net, the paper's Manhattan
mobility on it, and contacts by distance.

A copy of the program's ``fed.topology.grid_net`` and
``fed.mobility.ManhattanMobility`` at the time the benchmark was defined,
drawing from the same numpy generator in the same order, so that a seed
gives the same vehicle positions. Contacts are then worked out by brute
force: every pair within ``comm_range`` metres, and every vehicle with
itself, as a dense 0/1 [K, K] matrix per epoch.
"""
from __future__ import annotations

import math

import numpy as np


def grid(side: int, spacing: float):
    """Junction positions [side * side, 2] and adjacency lists of a
    ``side`` x ``side`` grid, ``spacing`` metres apart."""
    pos = np.array([[x * spacing, y * spacing] for y in range(side)
                    for x in range(side)], dtype=np.float64)
    adj: list[list[int]] = [[] for _ in range(side * side)]
    for y in range(side):
        for x in range(side):
            n = y * side + x
            if x + 1 < side:
                adj[n].append(n + 1)
                adj[n + 1].append(n)
            if y + 1 < side:
                adj[n].append(n + side)
                adj[n + side].append(n)
    return pos, adj


class Manhattan:
    """Vehicles on edges at a jittered constant speed; at a junction straight
    on with probability 0.5, else one of the other exits at random; a dead end
    turns back."""

    def __init__(self, pos, adj, num_vehicles: int, epoch_duration: float,
                 seed: int, speed: float = 13.89, jitter: float = 0.2):
        self.pos, self.adj = pos, adj
        self.k, self.duration = num_vehicles, epoch_duration
        self.rng = np.random.default_rng(seed)
        self.src = self.rng.integers(0, len(pos), size=num_vehicles)
        self.dst = np.array([self._any_exit(int(u)) for u in self.src])
        self.frac = self.rng.uniform(0, 1, size=num_vehicles)
        self.speed = speed * (1 + self.rng.uniform(-jitter, jitter,
                                                   size=num_vehicles))

    def _any_exit(self, u: int) -> int:
        return int(self.adj[u][self.rng.integers(0, len(self.adj[u]))])

    def _turn(self, prev: int, at: int) -> int:
        exits = list(self.adj[at])
        if len(exits) == 1:
            return exits[0]
        d_in = self.pos[at] - self.pos[prev]
        a_in = math.atan2(d_in[1], d_in[0])

        def deviation(v):
            d_out = self.pos[v] - self.pos[at]
            a = math.atan2(d_out[1], d_out[0]) - a_in
            return abs((a + math.pi) % (2 * math.pi) - math.pi)

        onward = sorted((v for v in exits if v != prev), key=deviation)
        if len(onward) == 1 or self.rng.random() < 0.5:
            return onward[0]
        rest = onward[1:]
        return int(rest[self.rng.integers(0, len(rest))])

    def _move(self) -> None:
        remaining = self.speed * self.duration
        for k in range(self.k):
            r = remaining[k]
            while r > 0:
                u, v = int(self.src[k]), int(self.dst[k])
                length = max(float(np.linalg.norm(self.pos[u] - self.pos[v])),
                             1e-6)
                left = (1.0 - self.frac[k]) * length
                if r < left:
                    self.frac[k] += r / length
                    r = 0.0
                else:
                    r -= left
                    self.src[k], self.dst[k] = v, self._turn(u, v)
                    self.frac[k] = 0.0

    def positions(self, num_epochs: int) -> np.ndarray:
        """[T, K, 2] positions at the end of each of the next T epochs."""
        out = np.empty((num_epochs, self.k, 2))
        for t in range(num_epochs):
            self._move()
            a, b = self.pos[self.src], self.pos[self.dst]
            out[t] = a + self.frac[:, None] * (b - a)
        return out


def contacts(positions: np.ndarray, comm_range: float) -> np.ndarray:
    """[T, K, 2] positions -> [T, K, K] float32 0/1 contacts, self included."""
    d = np.linalg.norm(positions[:, :, None, :] - positions[:, None, :, :],
                       axis=-1)
    c = (d <= comm_range).astype(np.float32)
    c[:, np.arange(c.shape[1]), np.arange(c.shape[1])] = 1.0
    return c
