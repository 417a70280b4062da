"""Operations and bytes that a federated round requires, from shapes.

Counts are of the work the algorithm needs, not of what the program does:
a straggler's sub-model counts only its alive units, and padding is not
work.  One multiply-add is two operations.  What a model needs per example
comes from its configuration's module (``bench/models/<model>.py``); the
kernels' own formulas are here.
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


def masked_matmul_cost(batch: int, k: int, n_alive: int) -> tuple:
    """(ops, bytes) of one masked dense layer's three kernel calls over its
    alive columns: y = x W, dx = dy W^T, dW = x^T dy, float32.  A masked
    contraction over alive rows, (batch, n_alive) @ (n_alive, k), costs
    the same."""
    ops = 3 * 2 * batch * k * n_alive
    words = (batch * k + k * n_alive + batch * n_alive) * 3
    return ops, 4 * words


def flash_attention_cost(batch: int, seq: int, heads: int,
                         head_dim: int) -> tuple:
    """(ops, bytes) of one causal flash-attention forward call, float32:
    QK^T and AV at their causal share (S^2/2 query-key pairs each), and q,
    k, v read and the output written once."""
    ops = 2 * batch * heads * seq * seq * head_dim
    return ops, 4 * 4 * batch * heads * seq * head_dim


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
        examples = tr["local_steps"] * tr["batch"]
        full = mod.train_ops(cfg, tr) * examples
        self.eval_ops = mod.forward_ops(cfg, tr) * mod.examples(tr)[1]
        per = {i: mod.train_ops(cfg, tr, m) * examples
               for i, m in masks.items()}
        self.train_ops = float(np.mean(
            [sum(per.get(i, full) for i in c) for c in cohorts]))
        self.ops_per_round = self.train_ops + self.eval_ops
        total = {}
        if tr["kernels"] == "pallas":
            for c in cohorts:
                for i in c:
                    costs = mod.kernel_costs(cfg, tr, masks.get(i))
                    for name, (o, b) in costs.items():
                        to, tb = total.get(name, (0, 0))
                        total[name] = (to + o * tr["local_steps"],
                                       tb + b * tr["local_steps"])
        #: per round, summed over every client's calls: {kernel: (ops,
        #: bytes)}
        self.kernels = {k: (o / len(cohorts), b / len(cohorts))
                        for k, (o, b) in total.items()}
        #: the masked-matmul pair's, as ``metrics/masked_matmul_roofline``
        #: reads them
        self.kernel_ops, self.kernel_bytes = self.kernels.get(
            "masked_matmul", (0.0, 0.0))
