"""Everything a cell is made of, from its files and the seed.

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``).  From those and ``--seed`` this module makes the
data (CIFAR-sized class-template images), the split of the data over the
clients, the fleet of capable devices and Table I stragglers, and the
initial weights, and builds the engine under test from them.  The same seed
gives the same inputs; the reference (``reference.py``) is given the same
inputs and nothing that the engine made.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench import models

HERE = os.path.dirname(os.path.abspath(__file__))


def read_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def seeds(seed: int) -> dict:
    """Independent 32-bit streams drawn from one seed of any size."""
    names = ("images", "eval", "parts", "weights", "engine")
    words = np.random.SeedSequence(seed).generate_state(len(names))
    return {n: int(w) for n, w in zip(names, words)}


def class_template_images(num: int, cfg: dict, seed: int,
                          template_seed: int = 1234, noise: float = 0.7):
    """(images (N, H, W, C) float32, labels (N,) int32): each class is a
    smooth random template (fixed by ``template_seed``), each image its
    class's template plus Gaussian noise.  A copy of the program's
    ``class_gaussian_images`` arithmetic, drawn in float32."""
    size, ch, classes = cfg["image_size"], cfg["in_channels"], \
        cfg["num_classes"]
    low = max(2, size // 4)
    templates = np.random.default_rng(template_seed).normal(
        size=(classes, low, low, ch)).astype(np.float32)
    reps = -(-size // low)
    templates = np.kron(templates, np.ones((1, reps, reps, 1), np.float32))[
        :, :size, :size, :]
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, size=num).astype(np.int32)
    images = rng.standard_normal((num, size, size, ch), dtype=np.float32)
    images *= noise
    images += templates[labels]
    return images, labels


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


def make_weights(cfg: dict, seed: int) -> dict:
    """LeCun-normal weights (standard deviation 1/sqrt(fan-in)) and zero
    biases, made on the device in one jitted call from the seed.  (With
    He-normal weights the AlexNet rounds diverge at lr 0.02.)"""
    shapes = models.load(cfg["model"]).param_shapes(cfg)

    def init(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if name.endswith("_b"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                fan_in = int(np.prod(shape[:-1]))
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32) \
                    * np.float32(1.0 / np.sqrt(fan_in))
        return out

    return jax.jit(init)(jax.random.PRNGKey(seed))


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
    images, labels = class_template_images(traffic["train_images"], cfg,
                                           s["images"])
    ti, tl = class_template_images(traffic["eval_images"], cfg, s["eval"])
    fl = fleet(traffic)
    order = np.random.default_rng(s["parts"]).permutation(len(labels))
    parts = [np.sort(p) for p in np.array_split(order, len(fl))]
    return World(workload["name"], cfg, traffic, seed,
                 {"images": images, "labels": labels},
                 {"images": ti, "labels": tl}, parts, fl,
                 make_weights(cfg, s["weights"]))


def build_engine(world: World):
    """The engine under test, with the world's clients, data and weights."""
    from repro.configs import CNNS, HeliosConfig
    from repro.federated import BatchedFLRun, make_fleet, setup_clients
    from repro.federated.runtime import ShardedFLRun

    cfg, tr = world.cfg, world.traffic
    if cfg["model"] == "alexnet":
        widths = {"cnn_channels": tuple(cfg["conv_channels"])}
    else:
        widths = {"cnn_channels": tuple(cfg["stage_channels"])}
    mcfg = dataclasses.replace(
        CNNS[cfg["model"]], image_size=cfg["image_size"],
        in_channels=cfg["in_channels"], num_classes=cfg["num_classes"],
        **widths)
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
    shapes = {k: tuple(v.shape) for k, v in run.global_params.items()}
    want = models.load(cfg["model"]).param_shapes(cfg)
    if shapes != want:
        raise RuntimeError(f"the program's parameters {shapes} are not the "
                           f"configuration's {want}")
    run.global_params = jax.tree.map(
        lambda w, old: jax.device_put(w, old.sharding), world.weights,
        run.global_params)
    return run
