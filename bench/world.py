"""Everything a cell is made of, from its files and the seed.

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``).  From those and ``--seed`` this module makes the
data and the initial weights (both from the configuration's module,
``bench/models/<model>.py``), the split of the data over the clients and
the fleet of capable devices and Table I stragglers, and builds the engine
under test from them.  The same seed gives the same inputs; the reference
(``reference.py``) is given the same inputs and nothing that the engine
made.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import numpy as np

from bench import compare, models

HERE = os.path.dirname(os.path.abspath(__file__))


def read_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def seeds(seed: int) -> dict:
    """Independent 32-bit streams drawn from one seed of any size."""
    names = ("train", "eval", "parts", "weights", "engine")
    words = np.random.SeedSequence(seed).generate_state(len(names))
    return {n: int(w) for n, w in zip(names, words)}


@dataclasses.dataclass
class World:
    """The inputs of one run of one cell."""
    name: str
    cfg: dict
    traffic: dict
    seed: int
    train: dict
    test: dict
    parts: list
    #: per client, in client order: (is_straggler, speed factor)
    fleet: list
    weights: dict

    @property
    def n_clients(self) -> int:
        return len(self.fleet)

    @property
    def units(self) -> int:
        """The model's maskable units, over every row."""
        return sum(rows * n for rows, n in models.load(
            self.cfg["model"]).mask_units(self.cfg).values())


def fleet(traffic: dict) -> list:
    """Capable clients first, then stragglers cycling through Table I."""
    speeds = traffic["straggler_speed"]
    return [(False, 1.0)] * traffic["capable"] + [
        (True, speeds[i % len(speeds)]) for i in range(traffic["stragglers"])]


def make_world(workload: dict, seed: int, cfg: dict | None = None,
               traffic: dict | None = None) -> World:
    """``cfg``/``traffic`` default to the cell's files (tests pass their
    own small ones)."""
    bench = read_json("..", "BENCHMARK.json") if cfg is None \
        or traffic is None else None
    if cfg is None:
        entry = next(c for c in bench["configs"]
                     if c["name"] == workload["config"])
        with open(os.path.join(HERE, "..", entry["file"])) as f:
            cfg = json.load(f)
    if traffic is None:
        traffic = read_json("traffic", workload["traffic"] + ".json")
    s = seeds(seed)
    mod = models.load(cfg["model"])
    train, test = mod.make_data(cfg, traffic, s)
    fl = fleet(traffic)
    order = np.random.default_rng(s["parts"]).permutation(
        len(next(iter(train.values()))))
    parts = [np.sort(p) for p in np.array_split(order, len(fl))]
    # the weights are made on the device in one jitted call from the seed
    weights = jax.jit(lambda key: mod.init(cfg, key))(
        jax.random.PRNGKey(s["weights"]))
    return World(workload["name"], cfg, traffic, seed, train, test, parts,
                 fl, weights)


def build_engine(world: World):
    """The engine under test, with the world's clients, data and weights."""
    from repro.configs import HeliosConfig
    from repro.federated import BatchedFLRun, make_fleet, setup_clients
    from repro.federated.runtime import ShardedFLRun

    cfg, tr = world.cfg, world.traffic
    mod = models.load(cfg["model"])
    mcfg = mod.program_config(cfg)
    hcfg = HeliosConfig(mask_block=tr["mask_block"], p_s=tr["p_s"],
                        min_volume=tr["min_volume"],
                        adapt_gain=tr["adapt_gain"],
                        aggregation=tr["aggregation"])
    clients = setup_clients(make_fleet(tr["capable"], tr["stragglers"]),
                            world.parts, hcfg)
    got = [(c.is_straggler, c.profile.speed_factor) for c in clients]
    if got != world.fleet:
        raise RuntimeError(f"the program's fleet {got[:8]}... is not the "
                           f"traffic file's {world.fleet[:8]}...")
    cls = {"batched": BatchedFLRun, "sharded": ShardedFLRun}[tr["engine"]]
    run = cls(mcfg, hcfg, tr["scheme"], clients, world.train, world.test,
              batch_size=tr["batch"], local_steps=tr["local_steps"],
              lr=tr["lr"], seed=seeds(world.seed)["engine"],
              eval_batch=tr["eval_batch"],
              participation=tr["participation"], kernels=tr["kernels"])
    shapes = {k: tuple(v.shape)
              for k, v in compare.leaves(run.global_params).items()}
    want = compare.leaves(mod.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    if shapes != want:
        raise RuntimeError(f"the program's parameters {shapes} are not the "
                           f"configuration's {want}")
    run.global_params = jax.tree.map(
        lambda w, old: jax.device_put(w, old.sharding), world.weights,
        run.global_params)
    return run
