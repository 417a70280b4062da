"""What the CIFAR classifiers (``alexnet``, ``resnet18``) share of the
module contract (``bench/models/__init__.py``): class-template images,
LeCun weights over a flat ``{name: array}`` tree, cross-entropy over the
logits, Eq. 1 over each unit's ``{unit}_w``/``{unit}_b`` pair, and work
counts from the model's layer table.

Each function reaches the model's own pieces (``WIDTHS``,
``param_shapes``, ``logits``, ``layers``, ``masked_matmul_layers``)
through ``cfg["model"]``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, models


def examples(traffic: dict) -> tuple:
    return traffic["train_images"], traffic["eval_images"]


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


def make_data(cfg: dict, traffic: dict, seeds: dict) -> tuple:
    n_train, n_eval = examples(traffic)
    images, labels = class_template_images(n_train, cfg, seeds["train"])
    ti, tl = class_template_images(n_eval, cfg, seeds["eval"])
    return {"images": images, "labels": labels}, \
        {"images": ti, "labels": tl}


def program_config(cfg: dict):
    from repro.configs import CNNS
    return dataclasses.replace(
        CNNS[cfg["model"]], image_size=cfg["image_size"],
        in_channels=cfg["in_channels"], num_classes=cfg["num_classes"],
        cnn_channels=tuple(cfg[models.load(cfg["model"]).WIDTHS]))


def init(cfg: dict, key) -> dict:
    """LeCun-normal weights (standard deviation 1/sqrt(fan-in)) and zero
    biases.  (With He-normal weights the AlexNet rounds diverge at lr
    0.02.)"""
    shapes = models.load(cfg["model"]).param_shapes(cfg)
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


def loss(params, batch, cfg, masks, precision):
    x, y = batch["images"], batch["labels"]
    z = models.load(cfg["model"]).logits(
        params, x.astype(params["head_w"].dtype), cfg, masks,
        precision).astype(jnp.float32)
    gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)


def unit_scores(new, old, cfg) -> dict:
    scores = {}
    for k in models.load(cfg["model"]).mask_units(cfg):
        d = jnp.abs(new[f"{k}_w"].astype(jnp.float32)
                    - old[f"{k}_w"].astype(jnp.float32))
        scores[k] = (d.sum(axis=tuple(range(d.ndim - 1)))
                     + jnp.abs(new[f"{k}_b"].astype(jnp.float32)
                               - old[f"{k}_b"].astype(jnp.float32)))[None]
    return scores


def alive_units(masks: dict) -> dict:
    return {k: int(np.sum(np.asarray(v) > 0)) for k, v in masks.items()}


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


def forward_ops(cfg: dict, traffic: dict) -> int:
    return 2 * forward_macs(cfg)


def train_ops(cfg: dict, traffic: dict, alive: dict | None = None) -> int:
    """Forward and backward operations of one image: forward 2 per MAC,
    weight gradient 2, input gradient 2 (not for the layer that reads the
    image)."""
    counts = None if alive is None else alive_units(alive)
    total = 0
    for l in models.load(cfg["model"]).layers(cfg):
        macs = layer_macs(l, counts)
        total += macs * (4 if l["first"] else 6)
    return total


def kernel_costs(cfg: dict, traffic: dict, masks: dict | None) -> dict:
    """The masked-matmul pair over each dense hidden layer's alive output
    blocks (the kernel runs a block when any of its units is alive)."""
    mod = models.load(cfg["model"])
    layers = {l["name"]: l for l in mod.layers(cfg)}
    ops = nbytes = 0
    for name in mod.masked_matmul_layers(cfg):
        l = layers[name]
        n = l["cout"] if masks is None else \
            flops.alive_block_units(masks[name], traffic["mask_block"])
        o, b = flops.masked_matmul_cost(traffic["batch"], l["cin"], n)
        ops += o
        nbytes += b
    return {"masked_matmul": (ops, nbytes)}
