"""Substrate tests: optimizers, compression, data, checkpoint, aggregation,
HLO cost model."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore, save
from repro.core import aggregation as AG
from repro.data.federated import label_distribution, partition_iid, partition_noniid
from repro.data.synthetic import batches, class_gaussian_images, markov_tokens
from repro.optim import (adamw, apply_updates, clip_by_global_norm,
                         compression, global_norm, momentum, sgd,
                         warmup_cosine_schedule)


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------


def test_sgd_step():
    opt = sgd(0.1)
    p = {"w": jnp.ones(3)}
    g = {"w": jnp.ones(3)}
    u, _ = opt.update(g, opt.init(p), p, 0)
    np.testing.assert_allclose(np.asarray(apply_updates(p, u)["w"]), 0.9)


def test_momentum_accumulates():
    opt = momentum(1.0, beta=0.5)
    p = {"w": jnp.zeros(1)}
    s = opt.init(p)
    g = {"w": jnp.ones(1)}
    u1, s = opt.update(g, s, p, 0)
    u2, s = opt.update(g, s, p, 1)
    assert float(u2["w"][0]) == -1.5                     # 1 + 0.5*1


def test_adamw_decays_matrices_not_vectors():
    opt = adamw(0.1, weight_decay=1.0)
    p = {"w": jnp.ones((2, 2)), "b": jnp.ones(2)}
    u, _ = opt.update({"w": jnp.zeros((2, 2)), "b": jnp.zeros(2)},
                      opt.init(p), p, 0)
    assert float(jnp.abs(u["w"]).max()) > 0.0            # decay applied
    assert float(jnp.abs(u["b"]).max()) == 0.0           # vectors exempt


def test_adamw_reduces_loss():
    key = jax.random.PRNGKey(0)
    w_true = jax.random.normal(key, (8,))
    x = jax.random.normal(jax.random.fold_in(key, 1), (64, 8))
    y = x @ w_true
    p = {"w": jnp.zeros(8)}
    opt = adamw(0.1)
    s = opt.init(p)
    loss = lambda p: jnp.mean((x @ p["w"] - y) ** 2)
    l0 = float(loss(p))
    for i in range(100):
        g = jax.grad(loss)(p)
        u, s = opt.update(g, s, p, i)
        p = apply_updates(p, u)
    assert float(loss(p)) < 0.05 * l0


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5
    assert float(norm) == 20.0


def test_warmup_cosine():
    s = warmup_cosine_schedule(1.0, 10, 100)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1.0) < 1e-6
    assert float(s(100)) <= 0.11


# ---------------------------------------------------------------------------
# compression (refs [19][20])
# ---------------------------------------------------------------------------


def test_topk_compression_keeps_largest():
    g = {"w": jnp.asarray([0.1, -5.0, 0.2, 3.0])}
    err = compression.init_error(g)
    sparse, new_err, frac = compression.compress(g, err, 0.5)
    np.testing.assert_allclose(np.asarray(sparse["w"]), [0, -5.0, 0, 3.0])
    np.testing.assert_allclose(np.asarray(new_err["w"]), [0.1, 0, 0.2, 0])
    assert abs(float(frac) - 0.5) < 1e-6


def test_error_feedback_preserves_mass():
    """Over cycles, error feedback transmits everything eventually."""
    g = {"w": jnp.asarray([1.0, 0.01, 0.005, 0.001])}
    err = compression.init_error(g)
    sent = jnp.zeros(4)
    for _ in range(16):
        sparse, err, _ = compression.compress(g, err, 0.25)
        sent = sent + sparse["w"]
    # average transmitted signal approaches cumulative gradient
    np.testing.assert_allclose(np.asarray(sent / 16), np.asarray(g["w"]),
                               atol=0.02)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def test_noniid_partition_skew():
    _, labels = class_gaussian_images(1000, 8, 1, 10, seed=0)
    parts = partition_noniid(labels, 5, shards_per_client=2)
    dist = label_distribution(labels, parts, 10)
    # each client sees only a few classes
    classes_per_client = (dist > 0).sum(axis=1)
    assert classes_per_client.max() <= 4
    # every sample assigned exactly once
    assert sum(len(p) for p in parts) == 1000


def test_iid_partition_covers():
    parts = partition_iid(100, 4)
    assert sorted(np.concatenate(parts).tolist()) == list(range(100))


def test_markov_tokens_learnable():
    toks = markov_tokens(4, 128, vocab=64, branching=4)
    assert toks.shape == (4, 128) and toks.max() < 64
    # successor entropy is low: repeated prefix pairs recur
    pairs = set(zip(toks[:, :-1].ravel(), toks[:, 1:].ravel()))
    assert len(pairs) < 64 * 16


def test_batches_iterator():
    xs = np.arange(10)
    it = batches((xs,), 3, epochs=2)
    seen = [b[0] for b in it]
    assert len(seen) == 6 and all(len(b) == 3 for b in seen)


# ---------------------------------------------------------------------------
# checkpoint / fault tolerance
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
            "opt": [{"m": jnp.ones(3)}, {"v": jnp.zeros(3)}],
            "step": jnp.asarray(7, jnp.int32)}
    save(str(tmp_path), 7, tree, metadata={"arch": "lenet"})
    got, step = restore(str(tmp_path), tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                  np.asarray(tree["params"]["w"]))
    assert int(got["step"]) == 7


def test_checkpoint_keep_n(tmp_path):
    tree = {"x": jnp.zeros(1)}
    for s in range(6):
        save(str(tmp_path), s, tree, keep=2)
    assert latest_step(str(tmp_path)) == 5
    files = [f for f in os.listdir(tmp_path) if f.endswith(".zst")]
    assert len(files) == 2


def test_checkpoint_restores_latest_after_crash(tmp_path):
    tree = {"x": jnp.asarray([1.0])}
    save(str(tmp_path), 1, tree)
    save(str(tmp_path), 2, {"x": jnp.asarray([2.0])})
    # simulate partial write of a newer checkpoint
    with open(os.path.join(tmp_path, "ckpt_3.msgpack.zst.tmp"), "wb") as f:
        f.write(b"garbage")
    got, step = restore(str(tmp_path), tree)
    assert step == 2 and float(got["x"][0]) == 2.0


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    save(str(tmp_path), 0, {"x": jnp.zeros(3)})
    with pytest.raises(ValueError):
        restore(str(tmp_path), {"x": jnp.zeros(4)})


# ---------------------------------------------------------------------------
# aggregation (Eq. 10 + variants)
# ---------------------------------------------------------------------------


def test_alpha_weights_eq10():
    a = AG.alpha_weights([1.0, 0.5, 0.5])
    np.testing.assert_allclose(np.asarray(a), [0.5, 0.25, 0.25])


def test_aggregate_alpha():
    g = {"w": jnp.zeros(2)}
    c1 = {"w": jnp.ones(2)}
    c2 = {"w": jnp.full(2, 3.0)}
    out = AG.aggregate_alpha(g, [c1, c2], [1.0, 1.0])
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0)


def test_masked_mean_respects_coverage():
    g = {"w": jnp.asarray([10.0, 10.0])}
    c1 = {"w": jnp.asarray([1.0, 99.0])}
    m1 = {"w": jnp.asarray([1.0, 0.0])}
    c2 = {"w": jnp.asarray([3.0, 98.0])}
    m2 = {"w": jnp.asarray([1.0, 0.0])}
    out = AG.aggregate_masked_mean(g, [c1, c2], [m1, m2])
    # coord 0 averaged over both; coord 1 untouched (nobody trained it)
    np.testing.assert_allclose(np.asarray(out["w"]), [2.0, 10.0])


def test_staleness_weight_decreases():
    assert AG.staleness_weight(0) == 1.0
    assert AG.staleness_weight(3) < AG.staleness_weight(1)


# ---------------------------------------------------------------------------
# trip-count-weighted HLO cost model
# ---------------------------------------------------------------------------


def test_hlo_weighted_cost_matches_unrolled():
    from repro.parallel.hlo_cost import weighted_cost

    def unrolled(x, w):
        for _ in range(6):
            x = jnp.tanh(x @ w)
        return x

    def scanned(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        x, _ = jax.lax.scan(body, x, None, length=6)
        return x

    x = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    cu = jax.jit(unrolled).lower(x, w).compile()
    cs = jax.jit(scanned).lower(x, w).compile()
    fu = weighted_cost(cu.as_text())["flops"]
    fs = weighted_cost(cs.as_text())["flops"]
    analytic = 6 * 2 * 64 * 256 * 256
    assert abs(fu - analytic) / analytic < 0.05
    assert abs(fs - analytic) / analytic < 0.05
    # XLA's own analysis under-counts the scanned program (the bug we fix)
    assert cs.cost_analysis()["flops"] < 0.5 * fs
