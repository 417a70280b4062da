"""CIFAR-scale AlexNet, written out plainly.

conv(3x3, SAME) -> ReLU -> unit mask, max-pool 2x2 after the layers named in
``pool_after``; flatten (NHWC order); two dense layers with ReLU and a unit
mask; a dense head.  The unit mask multiplies each layer's activation, so a
masked unit's weights get no gradient.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.models._cnn import (examples, forward_ops, init,  # noqa: F401
                               kernel_costs, loss, make_data,
                               program_config, train_ops, unit_scores)

#: the configuration's key that the program's ``cnn_channels`` takes
WIDTHS = "conv_channels"


def _side(cfg) -> int:
    return cfg["image_size"] // 2 ** len(cfg["pool_after"])


def param_shapes(cfg) -> dict:
    out, cin = {}, cfg["in_channels"]
    for i, c in enumerate(cfg["conv_channels"]):
        out[f"conv{i}_w"], out[f"conv{i}_b"] = (3, 3, cin, c), (c,)
        cin = c
    fin = _side(cfg) ** 2 * cin
    for i, n in enumerate(cfg["fc_units"]):
        out[f"fc{i}_w"], out[f"fc{i}_b"] = (fin, n), (n,)
        fin = n
    out["head_w"], out["head_b"] = (fin, cfg["num_classes"]), \
        (cfg["num_classes"],)
    return out


def mask_units(cfg) -> dict:
    """Maskable unit types in schema order: name -> (1, unit count)."""
    out = {f"conv{i}": (1, c) for i, c in enumerate(cfg["conv_channels"])}
    out.update({f"fc{i}": (1, n) for i, n in enumerate(cfg["fc_units"])})
    return out


def logits(params, x, cfg, masks, precision):
    """masks: {unit type: (1, n)} 0/1, or None for the whole model."""
    def mask(h, key):
        return h if masks is None else h * masks[key].astype(h.dtype)

    for i in range(len(cfg["conv_channels"])):
        x = jax.lax.conv_general_dilated(
            x, params[f"conv{i}_w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision) + params[f"conv{i}_b"]
        x = mask(jax.nn.relu(x), f"conv{i}")
        if i in cfg["pool_after"]:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    for i in range(len(cfg["fc_units"])):
        x = jnp.dot(x, params[f"fc{i}_w"], precision=precision) \
            + params[f"fc{i}_b"]
        x = mask(jax.nn.relu(x), f"fc{i}")
    return jnp.dot(x, params["head_w"], precision=precision) \
        + params["head_b"]


def layers(cfg) -> list:
    """Matmul-bearing layers for operation counts: output positions, kernel
    area, input and output widths, and the unit types that mask them
    (``in_rep`` input positions share one input unit after the flatten)."""
    out, side, cin, prev = [], cfg["image_size"], cfg["in_channels"], None
    for i, c in enumerate(cfg["conv_channels"]):
        out.append(dict(name=f"conv{i}", positions=side * side, kk=9,
                        cin=cin, cout=c, in_mask=prev, out_mask=f"conv{i}",
                        in_rep=1, first=i == 0))
        if i in cfg["pool_after"]:
            side //= 2
        cin, prev = c, f"conv{i}"
    rep = side * side
    fin = rep * cin
    for i, n in enumerate(cfg["fc_units"]):
        out.append(dict(name=f"fc{i}", positions=1, kk=1, cin=fin, cout=n,
                        in_mask=prev, out_mask=f"fc{i}", in_rep=rep,
                        first=False))
        fin, prev, rep = n, f"fc{i}", 1
    out.append(dict(name="head", positions=1, kk=1, cin=fin,
                    cout=cfg["num_classes"], in_mask=prev, out_mask=None,
                    in_rep=1, first=False))
    return out


def masked_matmul_layers(cfg) -> list:
    """Layers whose matmuls the engine runs on the masked-matmul kernel
    pair (the dense hidden layers; the convs are masked elementwise)."""
    return [f"fc{i}" for i in range(len(cfg["fc_units"]))]
