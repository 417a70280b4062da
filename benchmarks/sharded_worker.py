"""Subprocess worker for the sharded_population benchmark.

One invocation = one (host-device count, population size) cell: jax locks
its device count at first init, so the device sweep in benchmarks/run.py
spawns this worker with REPRO_HOST_DEVICES set per cell (the same forced
host-device pattern tests/test_dryrun_small.py validates).

  REPRO_HOST_DEVICES=16 python -m benchmarks.sharded_worker \
      --population 1024 --participation 32 --rounds 10
"""
import os

from repro.xla_env import force_host_devices

force_host_devices(os.environ.get("REPRO_HOST_DEVICES", "1"))

import argparse
import json
import time

import jax

from repro.configs import CNNS, HeliosConfig, reduced
from repro.data.federated import partition_iid_lazy
from repro.data.synthetic import class_gaussian_images
from repro.federated import ShardedFLRun, make_fleet, setup_clients


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="lenet")
    ap.add_argument("--population", type=int, default=1024)
    ap.add_argument("--participation", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--sampler", default="uniform")
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=16)
    args = ap.parse_args()

    cfg = reduced(CNNS[args.model])
    imgs, labels = class_gaussian_images(
        8192, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=0)
    ti, tl = class_gaussian_images(
        256, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=99)
    n = args.population
    parts = partition_iid_lazy(len(labels), n, seed=0)
    hcfg = HeliosConfig()
    t0 = time.perf_counter()
    clients = setup_clients(make_fleet(n - n // 2, n // 2), parts, hcfg)
    run = ShardedFLRun(cfg, hcfg, "helios", clients,
                       {"images": imgs, "labels": labels},
                       {"images": ti, "labels": tl},
                       local_steps=args.local_steps,
                       batch_size=args.batch_size, lr=0.05, seed=0,
                       participation=args.participation,
                       sampler=args.sampler)
    setup_s = time.perf_counter() - t0

    run.run_sync(1, eval_every=0)                 # compile warmup
    jax.block_until_ready(run.global_params)
    t0 = time.perf_counter()
    run.run_sync(args.rounds, eval_every=0)
    jax.block_until_ready(run.global_params)
    dt = time.perf_counter() - t0

    rec = {
        "model": args.model, "population": n,
        "participation": args.participation, "sampler": args.sampler,
        "devices": len(jax.devices()),
        "mesh_shards": int(run._mesh.devices.size),
        "kpad": run._kpad, "rounds": args.rounds,
        "rounds_per_sec": args.rounds / dt,
        "sec_per_round": dt / args.rounds,
        "setup_s": setup_s,
        # 1 == no recompile across sampled cohorts after warmup
        "compiled_programs": run._round_fn._cache_size(),
        "distinct_cohorts": len({tuple(c) for c in run.cohort_log}),
    }
    print("SHARDED " + json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
