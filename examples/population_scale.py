"""Population-scale federated rounds: partial participation + client
sharding.

A persistent population of N clients (half Table-I stragglers) keeps its
Helios soft-training state server-side while only a sampled cohort of K
trains each round — the regime real FL servers run in.  The round executes
as ONE shape-stable shard_map program over a ``("clients",)`` device mesh,
so the same script scales from this process's single device to a forced
multi-device host:

  PYTHONPATH=src python examples/population_scale.py \
      --population 1024 --participation 32 --rounds 10

  # 16-way client sharding (must be set before jax initializes -> env var):
  PYTHONPATH=src REPRO_HOST_DEVICES=16 python examples/population_scale.py \
      --population 4096 --participation 32 --sampler time_weighted
"""
import os

from repro.xla_env import force_host_devices

if os.environ.get("REPRO_HOST_DEVICES"):
    force_host_devices(os.environ["REPRO_HOST_DEVICES"])

import argparse
import time

import jax

from repro.configs import CNNS, HeliosConfig, reduced
from repro.data.federated import partition_iid_lazy
from repro.data.synthetic import class_gaussian_images
from repro.federated import ShardedFLRun, make_fleet, setup_clients


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="lenet",
                    choices=["lenet", "alexnet", "resnet18"])
    ap.add_argument("--population", type=int, default=1024)
    ap.add_argument("--participation", type=int, default=32)
    ap.add_argument("--sampler", default="uniform",
                    choices=["uniform", "time_weighted"])
    ap.add_argument("--rounds", type=int, default=10)
    args = ap.parse_args()

    cfg = reduced(CNNS[args.model])
    imgs, labels = class_gaussian_images(
        8192, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=0)
    ti, tl = class_gaussian_images(
        512, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=99)
    n, k = args.population, args.participation
    hcfg = HeliosConfig()
    # lazy partition: one shared permutation, no N per-client index arrays
    parts = partition_iid_lazy(len(labels), n, seed=0)
    clients = setup_clients(make_fleet(n - n // 2, n // 2), parts, hcfg)
    run = ShardedFLRun(cfg, hcfg, "helios", clients,
                       {"images": imgs, "labels": labels},
                       {"images": ti, "labels": tl},
                       local_steps=1, batch_size=16, lr=0.05,
                       participation=k, sampler=args.sampler)
    print(f"== {args.model}: N={n} clients, K={k}/round "
          f"({args.sampler}), {run._mesh.devices.size} mesh shard(s), "
          f"cohort padded to {run._kpad} ==")

    run.run_sync(1, eval_every=0)              # untimed compile warmup
    jax.block_until_ready(run.global_params)
    t0 = time.perf_counter()
    run.run_sync(args.rounds, eval_every=0)
    jax.block_until_ready(run.global_params)
    wall = time.perf_counter() - t0
    sampled = {i for cohort in run.cohort_log for i in cohort}
    print(f"{args.rounds} rounds in {wall:.1f}s "
          f"({args.rounds / wall:.2f} rounds/s) | acc {run.evaluate():.3f}")
    print(f"clients touched: {len(sampled)}/{n} | compiled round "
          f"programs: {run._round_fn._cache_size()} (shape-stable)")
    vols = sorted(c.volume for c in run.clients if c.is_straggler
                  and c.volume < 1.0)[:8]
    print(f"adapted straggler volumes (sampled cohorts only): "
          f"{[round(v, 2) for v in vols]}")


if __name__ == "__main__":
    main()
