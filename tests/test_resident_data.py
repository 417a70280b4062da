"""The training set resident on the device: host-drawn indices, one gather.

The bucketed, batched and sharded engines keep the training set on the
device when it fits (a quarter of a device's memory at most) and gather
each cohort's batches there from indices the host draws.  That must be a
pure change of where the lookup runs: the same draws, from the same RNG
calls, give the same arrays as numpy indexing on the host, so the engines'
trajectories match the host path bit for bit.
"""
import jax
import numpy as np
import pytest

import repro.federated.runtime as RT
from repro.configs import ARCHS, CNNS, HeliosConfig, reduced
from repro.data.federated import (partition_iid, partition_iid_lazy,
                                  partition_noniid)
from repro.data.synthetic import class_gaussian_images, markov_topic_tokens
from repro.federated import (AsyncFLRun, BatchedFLRun, ShardedFLRun,
                             make_fleet, setup_clients)
from repro.federated.adapter import DeviceData, fits_on_device, make_adapter

STEPS, BATCH = 2, 8


@pytest.fixture(scope="module")
def cnn():
    cfg = reduced(CNNS["lenet"])
    imgs, labels = class_gaussian_images(1200, cfg.image_size,
                                         cfg.in_channels, cfg.num_classes,
                                         seed=0)
    ti, tl = class_gaussian_images(256, cfg.image_size, cfg.in_channels,
                                   cfg.num_classes, seed=9)
    parts = partition_noniid(labels, 4, shards_per_client=4)
    return cfg, {"images": imgs, "labels": labels}, \
        {"images": ti, "labels": tl}, parts


@pytest.fixture(scope="module")
def lm():
    cfg = reduced(ARCHS["deepseek-7b"])
    tokens, _ = markov_topic_tokens(240, 32, 64, n_topics=8, seed=0)
    return cfg, {"tokens": tokens}


def _make(setting, cls, scheme="helios", **kw):
    cfg, train, test, parts = setting
    hcfg = HeliosConfig()
    clients = setup_clients(make_fleet(2, 2), parts, hcfg)
    return cls(cfg, hcfg, scheme, clients, train, test, local_steps=STEPS,
               batch_size=BATCH, lr=0.1, seed=0, eval_batch=64, **kw)


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# the fit rule, a pure function of (bytes, bytes_limit)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nbytes,limit,fits", [
    (10 ** 12, None, True),                 # no reported limit (the CPU)
    (0, 0, True),
    (100, 400, True),                       # exactly a quarter
    (101, 400, False),
    (100, 403, True),
    (614_400_000, 16 * 10 ** 9, True),      # CIFAR-sized set on a 16 GB chip
    (5 * 10 ** 9, 16 * 10 ** 9, False),
])
def test_fits_on_device_rule(nbytes, limit, fits):
    assert fits_on_device(nbytes, limit) is fits


@pytest.mark.parametrize("slack,on_device", [(0, True), (-1, False)],
                         ids=["quarter", "quarter-less-one"])
def test_engine_follows_fit_rule(cnn, monkeypatch, slack, on_device):
    """The engine asks the device for its limit and applies the rule: the
    host path runs only where the set takes more than a quarter."""
    nbytes = sum(v.nbytes for v in cnn[1].values())
    monkeypatch.setattr(RT, "device_bytes_limit",
                        lambda devices: 4 * nbytes + slack)
    run = _make(cnn, BatchedFLRun)
    run.run_sync(2, eval_every=0)
    assert isinstance(run._cohort_data, DeviceData) is on_device
    assert run.rec.count("sample_device_cohorts") == (2 if on_device else 0)
    assert run.rec.count("sample_host_cohorts") == (0 if on_device else 2)
    assert run.rec.snapshot()["gauges"]["resident_train_bytes"] \
        == (nbytes if on_device else 0)


# ---------------------------------------------------------------------------
# bit-identical draws: device gather vs numpy indexing
# ---------------------------------------------------------------------------


def _index_sets(kind: str, n: int):
    if kind == "few":           # fewer examples than steps x batch: replace
        return [np.arange(5), np.arange(7, 10), np.arange(20, 40)]
    if kind == "lazy":
        return [partition_iid_lazy(n, 6, seed=3)[i] for i in (0, 2, 5)]
    return partition_iid(n, 5, seed=3)


@pytest.mark.parametrize("family", ["cnn", "lm"])
@pytest.mark.parametrize("kind,pad_to,groups", [
    ("plain", 0, None),
    ("plain", 8, None),
    ("few", 4, None),
    ("lazy", 0, None),
    ("plain", 0, ([1, 3], [0, 2, 4])),
    ("plain", 0, ([], [0, 1, 2, 3, 4])),
])
def test_device_draws_match_host(cnn, lm, family, kind, pad_to, groups):
    """Same seed, same cohort: the gather returns numpy indexing's arrays
    element for element, in the same dtypes, and leaves the generator in
    the same state."""
    cfg, data = (cnn[0], cnn[1]) if family == "cnn" else lm
    adapter = make_adapter(cfg)
    n = adapter.num_examples(data)
    idx_seq = _index_sets(kind, n)
    if kind == "few":
        assert len(idx_seq[0]) < STEPS * BATCH
    rng_h, rng_d = np.random.default_rng(11), np.random.default_rng(11)
    host = adapter.sample_cohort(rng_h, data, idx_seq, STEPS, BATCH,
                                 pad_to=pad_to, groups=groups)
    dev = adapter.sample_cohort(rng_d, DeviceData(data), idx_seq, STEPS,
                                BATCH, pad_to=pad_to, groups=groups)
    assert rng_h.bit_generator.state == rng_d.bit_generator.state
    assert _tree_equal(host, dev)
    if groups is not None:
        assert [g is None for g in host] == [g is None for g in dev]
    rows = max(pad_to, len(idx_seq)) if groups is None else len(groups[1])
    lead = (dev if groups is None else dev[1])
    assert all(v.shape[:3] == (rows, STEPS, BATCH) for v in lead.values())
    if pad_to:                  # padding replicates slot 0's draw
        for v in dev.values():
            assert np.array_equal(np.asarray(v[-1]), np.asarray(v[0]))


# ---------------------------------------------------------------------------
# engines: device path vs forced host path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("participation", [0, 2], ids=["full", "sampled"])
def test_batched_device_path_matches_host_path(cnn, monkeypatch,
                                               participation):
    dev = _make(cnn, BatchedFLRun, participation=participation)
    hd = dev.run_sync(3)
    monkeypatch.setattr(RT, "fits_on_device", lambda nbytes, limit: False)
    host = _make(cnn, BatchedFLRun, participation=participation)
    hh = host.run_sync(3)
    assert not isinstance(host._cohort_data, DeviceData)
    assert _tree_equal(dev.global_params, host.global_params)
    assert [h["loss"] for h in hd] == [h["loss"] for h in hh]
    assert [h["ratios"] for h in hd] == [h["ratios"] for h in hh]
    # one cohort per round, each on the path the rule chose
    assert dev.rec.count("sample_device_cohorts") == 3
    assert dev.rec.count("sample_host_cohorts") == 0
    assert host.rec.count("sample_host_cohorts") == 3
    assert host.rec.count("sample_device_cohorts") == 0


def test_sharded_device_path_matches_host_path(cnn, monkeypatch):
    dev = _make(cnn, ShardedFLRun, participation=2)
    dev.run_sync(3)
    monkeypatch.setattr(RT, "fits_on_device", lambda nbytes, limit: False)
    host = _make(cnn, ShardedFLRun, participation=2)
    host.run_sync(3)
    assert _tree_equal(dev.global_params, host.global_params)
    assert dev.rec.count("sample_device_cohorts") == 3
    assert host.rec.count("sample_host_cohorts") == 3


def test_bucketed_device_path_matches_host_path(cnn, monkeypatch):
    dev = _make(cnn, AsyncFLRun, "afo")
    dev.run_async(6)
    monkeypatch.setattr(RT, "fits_on_device", lambda nbytes, limit: False)
    host = _make(cnn, AsyncFLRun, "afo")
    host.run_async(6)
    assert _tree_equal(dev.global_params, host.global_params)
    assert dev.bucket_sizes == host.bucket_sizes
    assert dev.rec.count("sample_device_cohorts") == len(dev.bucket_sizes)
    assert host.rec.count("sample_host_cohorts") == len(host.bucket_sizes)
    # one gather program per padded bucket size, like the bucket programs
    assert dev._cohort_data._gather._cache_size() \
        == len(dev.bucket_programs())
    assert set(dev.bucket_programs().values()) == {1}


def test_gather_compiles_once_per_cohort_shape(cnn):
    """Sampled batched cohorts change the straggler/capable split, and
    with it the gather's index shapes: one program per shape, none per
    round; the sharded engine's padded cohort keeps one shape."""
    bat = _make(cnn, BatchedFLRun, participation=2)
    bat.run_sync(6, eval_every=0)
    shapes = set()
    for c in bat.cohort_log:
        n_s = sum(bat.clients[i].is_straggler for i in c)
        shapes.add(tuple(n for n in (n_s, len(c) - n_s) if n))
    assert len(bat.cohort_log) == 6
    assert bat._cohort_data._gather._cache_size() == len(shapes)
    shd = _make(cnn, ShardedFLRun, participation=2)
    shd.run_sync(6, eval_every=0)
    assert len({tuple(c) for c in shd.cohort_log}) > 1
    assert shd._cohort_data._gather._cache_size() == 1


def test_resident_copy_made_once(cnn):
    """The copy is placed by the first sampled cohort, and elastic churn
    rebuilds the batched state, not the device copy."""
    run = _make(cnn, BatchedFLRun)
    assert run._cohort_data is None
    run.run_sync(1, eval_every=0)
    data = run._cohort_data
    run.remove_client(run.clients[-1].cid)
    run.run_sync(1, eval_every=0)
    assert run._cohort_data is data
    assert _tree_equal(data.arrays, cnn[1])


def test_no_copy_without_cohort_sampling(cnn):
    """A bucketed engine whose rounds never sample cohorts (the sequential
    sync loop) holds no device copy of the training set."""
    run = _make(cnn, AsyncFLRun)
    run.run_sync(2, eval_every=0)
    assert run._cohort_data is None
    assert run.rec.count("sample_device_cohorts") == 0
    assert "resident_train_bytes" not in run.rec.snapshot()["gauges"]
