"""Child process for the 16-host-device sharded equivalence tests.

Run by tests/test_sharded_engine.py in a SUBPROCESS (own XLA_FLAGS, like
tests/test_dryrun_small.py) so the forced host-device count never disturbs
the parent's single-device jax.  Runs all three engines — sequential,
batched, and client-sharded — on the same fixed-seed setting and prints one
JSON line per scheme with the pairwise max param diffs, and the sharded
engine's diff against its own run on the host sampling path.  ``--gather``
instead compiles the sharded engine's cohort gather on a 4-device client
mesh and prints one JSON line: the collectives in the compiled program, the
batches' sharding, and whether they equal numpy indexing.

  REPRO_HOST_DEVICES=16 python tests/sharded_equiv_child.py --family cnn
  REPRO_HOST_DEVICES=16 python tests/sharded_equiv_child.py --gather
"""
import os

from repro.xla_env import force_host_devices

force_host_devices(os.environ.get("REPRO_HOST_DEVICES", "16"))

import argparse
import json

import jax
import numpy as np

from repro.configs import ARCHS, CNNS, HeliosConfig, reduced
from repro.data.federated import partition_by_topic, partition_noniid
from repro.data.synthetic import class_gaussian_images, markov_topic_tokens
import repro.federated.runtime as RT
from repro.federated import (BatchedFLRun, FLRun, ShardedFLRun, make_fleet,
                             setup_clients)
from repro.federated.adapter import DeviceData
from repro.launch.mesh import make_client_mesh

#: what a cross-device gather would compile to
COLLECTIVES = ("all-gather", "all-to-all", "collective-permute",
               "all-reduce")


def _max_param_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _setting(family: str):
    if family == "cnn":
        cfg = reduced(CNNS["lenet"])
        imgs, labels = class_gaussian_images(
            1200, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=0)
        ti, tl = class_gaussian_images(
            256, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=9)
        parts = partition_noniid(labels, 4, shards_per_client=4)
        return (cfg, {"images": imgs, "labels": labels},
                {"images": ti, "labels": tl}, parts)
    cfg = reduced(ARCHS["deepseek-7b"])                  # small dense LM
    tokens, topics = markov_topic_tokens(240, 32, 64, n_topics=8, seed=0)
    test_tokens, _ = markov_topic_tokens(64, 32, 64, n_topics=8, seed=9)
    parts = partition_by_topic(topics, 4, topics_per_client=2)
    return cfg, {"tokens": tokens}, {"tokens": test_tokens}, parts


def gather_check() -> dict:
    """The sharded gather on a 4-device client mesh: rows split over the
    ``clients`` axis, each device reading its own copy."""
    _, train, _, _ = _setting("cnn")
    data = DeviceData(train, make_client_mesh(4))
    takes = np.random.default_rng(0).integers(
        0, len(train["labels"]), size=(8, 2, 32))
    (out,) = data.gather([takes])
    idx = jax.device_put((takes.astype(np.int32),), jax.sharding.NamedSharding(
        data.mesh, jax.sharding.PartitionSpec("clients")))
    hlo = data._gather.lower(data.arrays, idx).compile().as_text()
    return {
        "n_devices": len(jax.devices()),
        "mesh_shards": int(data.mesh.devices.size),
        "collectives": [c for c in COLLECTIVES if c in hlo],
        "specs": sorted({str(v.sharding.spec) for v in out.values()}),
        "equal": all(np.array_equal(np.asarray(out[k]), v[takes])
                     for k, v in train.items()),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=["cnn", "lm"], default="cnn")
    ap.add_argument("--schemes", default="helios,syn,st_only")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--gather", action="store_true")
    args = ap.parse_args()
    if args.gather:
        print("GATHER " + json.dumps(gather_check()), flush=True)
        return

    cfg, train, test, parts = _setting(args.family)
    fits = RT.fits_on_device
    for scheme in args.schemes.split(","):
        engines = {}
        hists = {}
        for name, cls in (("seq", FLRun), ("bat", BatchedFLRun),
                          ("shd", ShardedFLRun), ("shd_host", ShardedFLRun)):
            hcfg = HeliosConfig()
            clients = setup_clients(make_fleet(2, 2), parts, hcfg)
            # shd_host: the same engine, told that no training set fits
            RT.fits_on_device = fits if name != "shd_host" \
                else (lambda nbytes, limit: False)
            run = cls(cfg, hcfg, scheme, clients, train, test,
                      local_steps=2, batch_size=4 if args.family == "lm"
                      else 32, lr=0.1, seed=0, eval_batch=64)
            hists[name] = run.run_sync(args.rounds)
            engines[name] = run
        RT.fits_on_device = fits
        rec = {
            "family": args.family, "scheme": scheme,
            "n_devices": len(jax.devices()),
            "mesh_shards": int(engines["shd"]._mesh.devices.size),
            "diff_seq_bat": _max_param_diff(engines["seq"].global_params,
                                            engines["bat"].global_params),
            "diff_seq_shd": _max_param_diff(engines["seq"].global_params,
                                            engines["shd"].global_params),
            "diff_bat_shd": _max_param_diff(engines["bat"].global_params,
                                            engines["shd"].global_params),
            "diff_shd_host": _max_param_diff(
                engines["shd"].global_params,
                engines["shd_host"].global_params),
            "device_cohorts": engines["shd"].rec.count(
                "sample_device_cohorts"),
            "host_cohorts": engines["shd_host"].rec.count(
                "sample_host_cohorts"),
            "ratios_equal": all(
                np.allclose(a["ratios"], b["ratios"], atol=1e-6)
                for a, b in zip(hists["seq"], hists["shd"])),
            "times_equal": all(
                abs(a["time"] - b["time"]) < 1e-9
                for a, b in zip(hists["seq"], hists["shd"])),
        }
        print("EQUIV " + json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
