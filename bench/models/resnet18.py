"""CIFAR ResNet-18 with affine-free GroupNorm, written out plainly.

stem conv(3x3) -> GN -> ReLU; four stages of two basic blocks:
h = ReLU(GN(conv3x3(x, stride))) * mask; h = GN(conv3x3(h)); the shortcut is
a 1x1 projection (with the block's stride) where the width changes;
x = ReLU(x + h).  Global mean pool, dense head.  The stride is 2 in the first
block of every stage but the first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.models._cnn import (examples, forward_ops, init,  # noqa: F401
                               kernel_costs, loss, make_data,
                               program_config, train_ops, unit_scores)

#: the configuration's key that the program's ``cnn_channels`` takes
WIDTHS = "stage_channels"


def param_shapes(cfg) -> dict:
    ws = cfg["stage_channels"]
    out = {"stem_w": (3, 3, cfg["in_channels"], ws[0]), "stem_b": (ws[0],)}
    cin = ws[0]
    for s, w in enumerate(ws):
        for b in range(2):
            out[f"s{s}b{b}c0_w"] = (3, 3, cin if b == 0 else w, w)
            out[f"s{s}b{b}c0_b"] = (w,)
            out[f"s{s}b{b}c1_w"], out[f"s{s}b{b}c1_b"] = (3, 3, w, w), (w,)
            if b == 0 and cin != w:
                out[f"s{s}proj_w"], out[f"s{s}proj_b"] = (1, 1, cin, w), (w,)
        cin = w
    out["head_w"] = (ws[-1], cfg["num_classes"])
    out["head_b"] = (cfg["num_classes"],)
    return out


def mask_units(cfg) -> dict:
    return {f"s{s}b{b}c0": (1, w)
            for s, w in enumerate(cfg["stage_channels"]) for b in range(2)}


def _group_norm(x, groups, eps=1e-5):
    n, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(n, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    return ((xg - mu) * jax.lax.rsqrt(var + eps)).reshape(n, h, w, c)


def logits(params, x, cfg, masks, precision):
    def conv(x, name, stride=1):
        return jax.lax.conv_general_dilated(
            x, params[f"{name}_w"], (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision) + params[f"{name}_b"]

    groups = cfg["norm_groups"]
    x = jax.nn.relu(_group_norm(conv(x, "stem"), groups))
    cin = cfg["stage_channels"][0]
    for s, w in enumerate(cfg["stage_channels"]):
        for b in range(2):
            stride = 2 if (b == 0 and s > 0) else 1
            key = f"s{s}b{b}c0"
            h = jax.nn.relu(_group_norm(conv(x, key, stride), groups))
            if masks is not None:
                h = h * masks[key].astype(h.dtype)
            h = _group_norm(conv(h, f"s{s}b{b}c1"), groups)
            if b == 0 and cin != w:
                x = conv(x, f"s{s}proj", stride)
            x = jax.nn.relu(x + h)
        cin = w
    x = x.mean(axis=(1, 2))
    return jnp.dot(x, params["head_w"], precision=precision) \
        + params["head_b"]


def layers(cfg) -> list:
    side, cin = cfg["image_size"], cfg["in_channels"]
    ws = cfg["stage_channels"]
    out = [dict(name="stem", positions=side * side, kk=9, cin=cin,
                cout=ws[0], in_mask=None, out_mask=None, in_rep=1,
                first=True)]
    cin = ws[0]
    for s, w in enumerate(ws):
        for b in range(2):
            if b == 0 and s > 0:
                side //= 2
            key = f"s{s}b{b}c0"
            out.append(dict(name=key, positions=side * side, kk=9,
                            cin=cin if b == 0 else w, cout=w, in_mask=None,
                            out_mask=key, in_rep=1, first=False))
            out.append(dict(name=f"s{s}b{b}c1", positions=side * side, kk=9,
                            cin=w, cout=w, in_mask=key, out_mask=None,
                            in_rep=1, first=False))
            if b == 0 and cin != w:
                out.append(dict(name=f"s{s}proj", positions=side * side,
                                kk=1, cin=cin, cout=w, in_mask=None,
                                out_mask=None, in_rep=1, first=False))
        cin = w
    out.append(dict(name="head", positions=1, kk=1, cin=ws[-1],
                    cout=cfg["num_classes"], in_mask=None, out_mask=None,
                    in_rep=1, first=False))
    return out


def masked_matmul_layers(cfg) -> list:
    """No layer of this model runs on the masked-matmul kernel pair."""
    return []
