"""The plain decoder reference (``bench/models/decoder.py``) on its own:
causal, masks that multiply, Eq. 1 and the work counts by hand, at the
tiny size of ``data/tiny-decoder.json``."""
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench.models import decoder as D

DATA = os.path.join(os.path.dirname(__file__), "data")
HI = jax.lax.Precision.HIGHEST


def _read(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    cfg = _read("tiny-decoder")
    params = jax.jit(lambda k: D.init(cfg, k))(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg["vocab_size"])
    return cfg, params, tokens


def _ones(cfg):
    return {k: jnp.ones(shape) for k, shape in D.mask_units(cfg).items()}


def test_init_shapes_and_norm_scales(tiny):
    cfg, params, _ = tiny
    assert jax.tree.map(jnp.shape, params) == D.param_shapes(cfg)
    assert float(jnp.min(params["blocks"]["mlp_norm"]["scale"])) == 1.0
    assert abs(float(jnp.std(params["blocks"]["attn"]["wq"])) - 0.02) < 2e-3


def test_a_later_token_leaves_earlier_logits_unchanged(tiny):
    cfg, params, tokens = tiny
    later = tokens.at[:, 10].set((tokens[:, 10] + 1) % cfg["vocab_size"])
    a = np.asarray(D.logits(params, tokens, cfg, None, HI))
    b = np.asarray(D.logits(params, later, cfg, None, HI))
    np.testing.assert_allclose(a[:, :10], b[:, :10], rtol=1e-6, atol=1e-6)
    assert np.abs(a[:, 10:] - b[:, 10:]).max() > 1e-3


def test_all_ones_masks_equal_no_masks(tiny):
    cfg, params, tokens = tiny
    batch = {"tokens": tokens}
    assert float(D.loss(params, batch, cfg, _ones(cfg), HI)) \
        == float(D.loss(params, batch, cfg, None, HI))


@pytest.mark.parametrize("unit", ["heads", "mlp"])
def test_a_masked_unit_gets_zero_gradient(tiny, unit):
    cfg, params, tokens = tiny
    masks = _ones(cfg)
    masks[unit] = masks[unit].at[1, 2].set(0.0)
    grad = jax.grad(D.loss)
    g = grad(params, {"tokens": tokens}, cfg, masks, HI)["blocks"]
    full = grad(params, {"tokens": tokens}, cfg, None, HI)["blocks"]
    if unit == "heads":
        a, fa = g["attn"], full["attn"]
        cut = [a[w][1, :, 2] for w in ("wq", "wk", "wv")] + [a["wo"][1, 2]]
        kept = [fa[w][1, :, 2] for w in ("wq", "wk", "wv")] + [fa["wo"][1, 2]]
    else:
        m, fm = g["mlp"], full["mlp"]
        cut = [m["wi"][1, :, 2], m["wg"][1, :, 2], m["wo"][1, 2]]
        kept = [fm["wi"][1, :, 2], fm["wg"][1, :, 2], fm["wo"][1, 2]]
    for c, k in zip(cut, kept):
        assert float(jnp.max(jnp.abs(c))) == 0.0
        assert float(jnp.max(jnp.abs(k))) > 0.0


def test_unit_scores_by_hand(tiny):
    cfg, old, _ = tiny
    leaves, tree = jax.tree.flatten(old)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    new = jax.tree.unflatten(tree, [
        x + 0.01 * jax.random.normal(k, x.shape) for x, k in
        zip(leaves, keys)])
    got = D.unit_scores(new, old, cfg)
    a = {w: np.abs(np.asarray(new["blocks"]["attn"][w], np.float64)
                   - np.asarray(old["blocks"]["attn"][w], np.float64))
         for w in ("wq", "wk", "wv", "wo")}
    m = {w: np.abs(np.asarray(new["blocks"]["mlp"][w], np.float64)
                   - np.asarray(old["blocks"]["mlp"][w], np.float64))
         for w in ("wi", "wg", "wo")}
    L, H, F = cfg["num_layers"], cfg["num_heads"], cfg["d_ff"]
    heads = np.zeros((L, H))
    mlp = np.zeros((L, F))
    for l in range(L):
        for h in range(H):
            heads[l, h] = (a["wq"][l, :, h].sum() + a["wk"][l, :, h].sum()
                           + a["wv"][l, :, h].sum() + a["wo"][l, h].sum())
        for f in range(F):
            mlp[l, f] = (m["wi"][l, :, f].sum() + m["wg"][l, :, f].sum()
                         + m["wo"][l, f].sum())
    np.testing.assert_allclose(got["heads"], heads, rtol=1e-5)
    np.testing.assert_allclose(got["mlp"], mlp, rtol=1e-5)


def _masks():
    """Layer 0: heads 0 and 3 and the first 128-unit block plus one unit of
    the fourth; layer 1: head 1 and the second block."""
    heads = np.zeros((2, 4), np.float32)
    heads[0, [0, 3]] = heads[1, 1] = 1
    mlp = np.zeros((2, 512), np.float32)
    mlp[0, :128] = mlp[0, 400] = 1
    mlp[1, 128:256] = 1
    return {"heads": heads, "mlp": mlp}


def test_work_counts_by_hand():
    cfg, tr = _read("tiny-decoder"), _read("tiny-token-stragglers")
    # S 32, d 64, hd 16, H 4, d_ff 512, V 256; MACs per sequence and layer:
    # projections 4 S d hd H, QK^T and AV at the causal share S^2 hd H,
    # the MLP 3 S d d_ff; the output projection S d V once
    layer = 4 * 32 * 64 * 16 * 4 + 32 * 32 * 16 * 4 + 3 * 32 * 64 * 512
    full = 2 * (2 * layer + 32 * 64 * 256)
    assert full == 15_990_784
    assert D.forward_ops(cfg, tr) == full
    assert D.train_ops(cfg, tr) == 3 * full
    # alive: layer 0 2 heads and 129 units, layer 1 1 head and 128 units
    l0 = 4 * 32 * 64 * 16 * 2 + 32 * 32 * 16 * 2 + 3 * 32 * 64 * 129
    l1 = 4 * 32 * 64 * 16 * 1 + 32 * 32 * 16 * 1 + 3 * 32 * 64 * 128
    assert D.train_ops(cfg, tr, _masks()) == 3 * 2 * (l0 + l1 + 32 * 64 * 256)
    # kernels, per local step of one client (batch 4, so 128 rows): wi, wg
    # and wo over the alive blocks (layer 0: 2 blocks, layer 1: 1), three
    # calls each; flash attention forward over the alive heads
    mm_ops = 3 * 3 * 2 * 128 * 64 * (256 + 128)
    mm_bytes = 3 * 4 * 3 * (2 * 128 * 64 + 64 * 384 + 128 * 384)
    fa_ops = 2 * 4 * (2 + 1) * 32 * 32 * 16
    fa_bytes = 4 * 4 * 4 * (2 + 1) * 32 * 16
    assert D.kernel_costs(cfg, tr, _masks()) == {
        "masked_matmul": (mm_ops, mm_bytes),
        "flash_attention": (fa_ops, fa_bytes)}
    assert (mm_ops, mm_bytes, fa_ops, fa_bytes) == (
        56_623_104, 3_244_032, 393_216, 98_304)
    whole = D.kernel_costs(cfg, tr, None)
    assert whole["flash_attention"] == flops.flash_attention_cost(
        4, 32, 8, 16)
    assert whole["masked_matmul"][0] == 3 * 3 * 2 * 128 * 64 * 1024


def test_round_cost_sums_every_kernel():
    cfg, tr = _read("tiny-decoder"), _read("tiny-token-stragglers")
    world = types.SimpleNamespace(cfg=cfg, traffic=tr)
    cost = flops.RoundCost(world, [[0, 1, 2, 3]], {2: _masks(), 3: _masks()})
    part = D.kernel_costs(cfg, tr, _masks())
    whole = D.kernel_costs(cfg, tr, None)
    steps = tr["local_steps"]
    for k in ("masked_matmul", "flash_attention"):
        assert cost.kernels[k] == tuple(
            float(steps * (2 * w + 2 * p)) for w, p in zip(whole[k], part[k]))
    assert (cost.kernel_ops, cost.kernel_bytes) == cost.kernels[
        "masked_matmul"]
    assert cost.train_ops == 4 * 4 * 2 * 0.5 * (
        D.train_ops(cfg, tr) + D.train_ops(cfg, tr, _masks()))
    assert cost.eval_ops == D.forward_ops(cfg, tr) * 64
