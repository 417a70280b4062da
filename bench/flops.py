"""Operations and bytes that a federated round requires, from shapes.

Counts are of the work the algorithm needs, not of what the program does:
a straggler's sub-model counts only its alive units (a layer's MACs scale
with alive inputs x alive outputs), the first layer's input gradient is not
needed, and padding is not work.  One multiply-add is two operations.
"""
from __future__ import annotations

import json
import os

import numpy as np

from bench import models

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip (``peaks.json``); an unknown kind is an
    error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in bench/peaks.json")
    return table[device_kind]


def layer_macs(layer: dict, alive: dict | None) -> int:
    """MACs of one layer for one image; ``alive`` maps unit type -> alive
    count (None: the whole model)."""
    cin, cout = layer["cin"], layer["cout"]
    if alive is not None and layer["in_mask"] is not None:
        cin = alive[layer["in_mask"]] * layer["in_rep"]
    if alive is not None and layer["out_mask"] is not None:
        cout = alive[layer["out_mask"]]
    return layer["positions"] * layer["kk"] * cin * cout


def forward_macs(cfg: dict, alive: dict | None = None) -> int:
    return sum(layer_macs(l, alive)
               for l in models.load(cfg["model"]).layers(cfg))


def train_ops(cfg: dict, alive: dict | None = None) -> int:
    """Forward and backward operations of one image: forward 2 MACs, weight
    gradient 2, input gradient 2 (not for the layer that reads the
    image)."""
    total = 0
    for l in models.load(cfg["model"]).layers(cfg):
        macs = layer_macs(l, alive)
        total += macs * (4 if l["first"] else 6)
    return total


def masked_matmul_cost(batch: int, k: int, n_alive: int) -> tuple:
    """(ops, bytes) of one masked dense layer's three kernel calls over its
    alive output columns: y = x W, dx = dy W^T, dW = x^T dy, float32."""
    ops = 3 * 2 * batch * k * n_alive
    words = (batch * k + k * n_alive + batch * n_alive) * 3
    return ops, 4 * words


def alive_units(masks: dict) -> dict:
    return {k: int(np.sum(np.asarray(v) > 0)) for k, v in masks.items()}


def alive_block_units(mask, block: int) -> int:
    """Units in the mask blocks that hold any alive unit (the kernel runs a
    block when any of its units is alive)."""
    m = np.asarray(mask).reshape(-1) > 0
    pad = (-len(m)) % block
    m = np.concatenate([m, np.zeros(pad, bool)]).reshape(-1, block)
    return int(m.any(axis=1).sum()) * block


class RoundCost:
    """Required work of the rounds of one window.

    ``cohorts``: each round's client list; ``masks``: {straggler: its unit
    masks}, read from the engine after the window (a straggler's unit
    counts follow its volume, which holds still)."""

    def __init__(self, world, cohorts: list, masks: dict):
        cfg, tr = world.cfg, world.traffic
        mod = models.load(cfg["model"])
        images = tr["local_steps"] * tr["batch"]
        full = train_ops(cfg) * images
        self.eval_ops = 2 * forward_macs(cfg) * tr["eval_images"]
        per = {i: train_ops(cfg, alive_units(m)) * images
               for i, m in masks.items()}
        self.train_ops = float(np.mean(
            [sum(per.get(i, full) for i in c) for c in cohorts]))
        self.ops_per_round = self.train_ops + self.eval_ops
        kops = kbytes = 0
        if tr["kernels"] == "pallas":
            layers = {l["name"]: l for l in mod.layers(cfg)}
            for c in cohorts:
                for i in c:
                    for name in mod.masked_matmul_layers(cfg):
                        l = layers[name]
                        n = l["cout"] if i not in masks else \
                            alive_block_units(masks[i][name],
                                              tr["mask_block"])
                        o, b = masked_matmul_cost(tr["batch"], l["cin"], n)
                        kops += o * tr["local_steps"]
                        kbytes += b * tr["local_steps"]
        #: per round, summed over every client's kernel calls
        self.kernel_ops = kops / len(cohorts)
        self.kernel_bytes = kbytes / len(cohorts)
