"""The three-way equivalence wall: FLRun ↔ BatchedFLRun ↔ ShardedFLRun.

The client-sharded engine must be a pure execution-layout change on top of
the batched engine: for a fixed seed all three engines produce the same
global params (atol 1e-5 over 3 rounds), the same per-round straggler
selected fractions, and the same simulated wall times — for the CNN testbed
AND a dense-LM family.  In-process tests run the sharded engine on this
process's (single-device) mesh; the multi-device path runs in a
16-host-device SUBPROCESS (own XLA_FLAGS, tests/sharded_equiv_child.py)
exactly like tests/test_dryrun_small.py.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.analysis import contracts as CT
from repro.configs import ARCHS, CNNS, HeliosConfig, reduced
from repro.data.federated import partition_by_topic, partition_noniid
from repro.data.synthetic import class_gaussian_images, markov_topic_tokens
from repro.federated import (BatchedFLRun, FLRun, ShardedFLRun, make_fleet,
                             setup_clients)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def setting():
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
def lm_setting():
    cfg = reduced(ARCHS["deepseek-7b"])
    tokens, topics = markov_topic_tokens(240, 32, 64, n_topics=8, seed=0)
    test_tokens, _ = markov_topic_tokens(64, 32, 64, n_topics=8, seed=9)
    parts = partition_by_topic(topics, 4, topics_per_client=2)
    return cfg, {"tokens": tokens}, {"tokens": test_tokens}, parts


def _make(setting, cls, scheme, hcfg=None, batch_size=32, **kw):
    cfg, train, test, parts = setting
    hcfg = hcfg or HeliosConfig()
    clients = setup_clients(make_fleet(2, 2), parts, hcfg)
    return cls(cfg, hcfg, scheme, clients, train, test,
               local_steps=2, batch_size=batch_size, lr=0.1, seed=0,
               eval_batch=64, **kw)


def _max_param_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("scheme", ["helios", "syn", "st_only"])
def test_sharded_matches_sequential_cnn(setting, scheme):
    """Fixed seed, 3 rounds: same global params, ratios, volumes, times."""
    seq = _make(setting, FLRun, scheme)
    shd = _make(setting, ShardedFLRun, scheme)
    hs = seq.run_sync(3)
    hh = shd.run_sync(3)
    assert _max_param_diff(seq.global_params, shd.global_params) < 1e-5
    for a, b in zip(hs, hh):
        np.testing.assert_allclose(a["ratios"], b["ratios"], atol=1e-6)
        np.testing.assert_allclose(a["volumes"], b["volumes"], atol=1e-6)
        assert abs(a["time"] - b["time"]) < 1e-9


def test_sharded_matches_batched_lm(lm_setting):
    """The dense-LM family federates identically through the sharded path
    (generic axis-driven masks + scores under shard_map)."""
    bat = _make(lm_setting, BatchedFLRun, "helios", batch_size=4)
    shd = _make(lm_setting, ShardedFLRun, "helios", batch_size=4)
    hb = bat.run_sync(3)
    hh = shd.run_sync(3)
    assert _max_param_diff(bat.global_params, shd.global_params) < 1e-5
    for a, b in zip(hb, hh):
        np.testing.assert_allclose(a["ratios"], b["ratios"], atol=1e-6)
        assert abs(a["ce"] - b["ce"]) < 1e-4


def test_sharded_masked_mean(setting):
    """The psum'd per-coordinate masked mean matches the sequential
    list-of-pytrees reference path."""
    hcfg = HeliosConfig(aggregation="masked_mean")
    seq = _make(setting, FLRun, "helios", hcfg=hcfg)
    shd = _make(setting, ShardedFLRun, "helios", hcfg=hcfg)
    seq.run_sync(2)
    shd.run_sync(2)
    assert _max_param_diff(seq.global_params, shd.global_params) < 1e-5


def test_sharded_shape_stable_no_recompile(setting):
    """Across many sampled cohorts the round program compiles EXACTLY once:
    cohort-shape-stable padding + traced soft/valid flags."""
    shd = _make(setting, ShardedFLRun, "helios", participation=2)
    shd.run_sync(5, eval_every=0)
    assert len({tuple(c) for c in shd.cohort_log}) > 1   # draws did vary
    # one round program total — asserted through the contracts API
    rep = CT.compile_report(shd)
    assert rep.get("round"), rep
    with CT.override(True):
        CT.check_compile_budget(shd)


def test_sharded_population_state_roundtrip(setting):
    """sync_client_states materializes rows; checkpoint-style snapshots see
    advanced cycles and compressed straggler masks."""
    shd = _make(setting, ShardedFLRun, "helios")
    shd.run_sync(2)
    shd.sync_client_states()
    for c in shd.clients:
        if c.is_straggler:
            assert int(c.helios_state["cycle"]) == 2
            fracs = [float(m.mean())
                     for m in c.helios_state["masks"].values()]
            assert min(fracs) < 0.9
        else:
            assert int(c.helios_state["cycle"]) == 0


def _child(*args, tag="EQUIV"):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               REPRO_HOST_DEVICES="16")
    cmd = [sys.executable, os.path.join(REPO, "tests",
                                        "sharded_equiv_child.py"), *args]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return [json.loads(line[len(tag) + 1:])
            for line in r.stdout.splitlines() if line.startswith(tag + " ")]


def _run_child(family, schemes="helios,syn,st_only", rounds=3):
    recs = _child("--family", family, "--schemes", schemes,
                  "--rounds", str(rounds))
    assert len(recs) == len(schemes.split(","))
    for rec in recs:
        # the device gather gives the host path's trajectory bit for bit
        assert rec["diff_shd_host"] == 0.0, rec
        assert rec["device_cohorts"] == rec["host_cohorts"] == rounds, rec
    return recs


def test_sharded_gather_16dev_no_collectives():
    """Compile-only on a 4-device client mesh: the sharded cohort gather
    splits its rows over ``clients`` and reads each device's own copy of
    the training set, so the program holds no collective."""
    (rec,) = _child("--gather", tag="GATHER")
    assert rec["n_devices"] == 16 and rec["mesh_shards"] == 4
    assert rec["collectives"] == [], rec
    assert rec["specs"] == ["PartitionSpec('clients',)"], rec
    assert rec["equal"], rec


@pytest.mark.slow
def test_sharded_equivalence_16dev_cnn():
    """CNN three-way wall on a real 16-host-device mesh (subprocess)."""
    for rec in _run_child("cnn"):
        assert rec["n_devices"] == 16
        assert rec["mesh_shards"] == 4          # capped at the cohort size
        assert rec["diff_seq_bat"] < 1e-5, rec
        assert rec["diff_seq_shd"] < 1e-5, rec
        assert rec["diff_bat_shd"] < 1e-5, rec
        assert rec["ratios_equal"] and rec["times_equal"], rec


@pytest.mark.slow
def test_sharded_equivalence_16dev_lm():
    """Dense-LM three-way wall on a 16-host-device mesh (subprocess)."""
    for rec in _run_child("lm", schemes="helios"):
        assert rec["n_devices"] == 16
        assert rec["diff_seq_shd"] < 1e-5, rec
        assert rec["diff_bat_shd"] < 1e-5, rec
        assert rec["ratios_equal"] and rec["times_equal"], rec
