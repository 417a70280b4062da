"""Child process for the 16-host-device sharded equivalence tests.

Run by tests/test_sharded_engine.py in a SUBPROCESS (own XLA_FLAGS, like
tests/test_dryrun_small.py) so the forced host-device count never disturbs
the parent's single-device jax.  Runs all three engines — sequential,
batched, and client-sharded — on the same fixed-seed setting and prints one
JSON line per scheme with the pairwise max param diffs.

  REPRO_HOST_DEVICES=16 python tests/sharded_equiv_child.py --family cnn
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
from repro.federated import (BatchedFLRun, FLRun, ShardedFLRun, make_fleet,
                             setup_clients)


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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=["cnn", "lm"], default="cnn")
    ap.add_argument("--schemes", default="helios,syn,st_only")
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()

    cfg, train, test, parts = _setting(args.family)
    for scheme in args.schemes.split(","):
        engines = {}
        hists = {}
        for name, cls in (("seq", FLRun), ("bat", BatchedFLRun),
                          ("shd", ShardedFLRun)):
            hcfg = HeliosConfig()
            clients = setup_clients(make_fleet(2, 2), parts, hcfg)
            run = cls(cfg, hcfg, scheme, clients, train, test,
                      local_steps=2, batch_size=4 if args.family == "lm"
                      else 32, lr=0.1, seed=0, eval_batch=64)
            hists[name] = run.run_sync(args.rounds)
            engines[name] = run
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
