"""Model FLOPs of a federation, counted from shapes, and the chip's peak.

A "sample" is the unit the batch counts: an image, or a sequence; each
configuration module's ``forward_flops_per_sample()`` counts one. Training
counts forward and backward as three forward passes per sample, for
K * E * B samples an epoch; the in-scan eval is one forward pass per vehicle
and eval sample on each evaluated epoch. Operations the program adds (the
im2col patches, P1, the gossip mix) are not model FLOPs and are not counted.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def federation_flops(cell) -> float:
    """Model FLOPs of one whole federation of ``cell``."""
    c, t = cell.config, cell.traffic
    fwd = cell.model.forward_flops_per_sample()
    k, epochs = t["num_vehicles"], t["federation_epochs"]
    evals = sum((e + 1) % t["eval_every"] == 0 or e == epochs - 1
                for e in range(epochs))
    train = 3 * fwd * k * c["local_steps"] * c["batch_size"] * epochs
    return train + fwd * k * c["eval_samples"] * evals


def peak_flops(device_kind: str) -> float:
    """bf16 FLOP/s of one chip of ``device_kind``; an unknown kind is an
    error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peak for device kind {device_kind!r} in {PEAKS}")
    return float(table[device_kind]["bf16_flops_per_s"])
