"""Subprocess worker for the million_population benchmark.

One invocation = one (population N, compression mode) cell of
``benchmarks/run.py --only million_population``: the sharded engine with
K-client participation over an N-client population, uplink compression
on, peak host RSS measured over the whole process lifetime
(``resource.getrusage``) so population setup counts against the stated
memory budget.

Population construction is deliberately lean: straggler identification /
volume assignment run once over an 8-profile TEMPLATE fleet (the paper's
heterogeneity settings) and the N clients cycle those templates — the
O(N * stragglers) membership scan of ``setup_clients`` would dominate at
N=10^6 without changing what the bench measures.  All clients share ONE
data-index array (the bench axis is population state + uplink volume,
not dataset size).

  python -m benchmarks.million_worker --population 1000000 \
      --participation 64 --rounds 3 --mode topk
"""
import os

from repro.xla_env import force_host_devices

force_host_devices(os.environ.get("REPRO_HOST_DEVICES", "1"))

import argparse
import json
import resource
import time

import jax
import numpy as np

from repro.configs import CNNS, HeliosConfig, reduced
from repro.data.synthetic import class_gaussian_images
from repro.federated import ShardedFLRun, make_fleet, setup_clients
from repro.federated.runtime import Client


def build_population(n: int, data_len: int, hcfg: HeliosConfig):
    """N clients cycling an 8-profile identified template fleet."""
    tmpl = setup_clients(make_fleet(4, 4), [np.arange(8)] * 8, hcfg)
    idx = np.arange(data_len)
    return [Client(cid=i, profile=tmpl[i % 8].profile, data_idx=idx,
                   volume=tmpl[i % 8].volume,
                   is_straggler=tmpl[i % 8].is_straggler)
            for i in range(n)]


def peak_rss_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="lenet")
    ap.add_argument("--population", type=int, default=4096)
    ap.add_argument("--participation", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--mode", default="none",
                    choices=("none", "topk", "quant", "delta"))
    ap.add_argument("--frac", type=float, default=0.05)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=16)
    args = ap.parse_args()

    cfg = reduced(CNNS[args.model])
    imgs, labels = class_gaussian_images(
        4096, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=0)
    ti, tl = class_gaussian_images(
        256, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=99)
    hcfg = HeliosConfig()
    t0 = time.perf_counter()
    clients = build_population(args.population, len(labels), hcfg)
    run = ShardedFLRun(cfg, hcfg, "helios", clients,
                       {"images": imgs, "labels": labels},
                       {"images": ti, "labels": tl},
                       local_steps=args.local_steps,
                       batch_size=args.batch_size, lr=0.05, seed=0,
                       participation=args.participation,
                       compression=args.mode, comp_frac=args.frac,
                       comp_bits=args.bits)
    setup_s = time.perf_counter() - t0

    run.run_sync(1, eval_every=0)                 # compile warmup
    jax.block_until_ready(run.global_params)
    t0 = time.perf_counter()
    run.run_sync(args.rounds, eval_every=0)
    jax.block_until_ready(run.global_params)
    dt = time.perf_counter() - t0

    total_rounds = args.rounds + 1                # warmup included in bytes
    rec = {
        "model": args.model, "population": args.population,
        "participation": args.participation, "mode": args.mode,
        "frac": args.frac, "bits": args.bits, "rounds": args.rounds,
        "rounds_per_sec": args.rounds / dt,
        "sec_per_round": dt / args.rounds,
        "setup_s": setup_s,
        "peak_host_bytes": peak_rss_bytes(),
        "pop_state_bytes": sum(
            x.nbytes for x in jax.tree.leaves(run._pop_state)),
        "error_store_bytes": (run._err_store.nbytes()
                              if args.mode != "none" else 0),
        "error_rows_touched": (run._err_store.touched()
                               if args.mode != "none" else 0),
        "uplink_bytes_total": run.uplink_bytes(),
        "uplink_bytes_per_round": run.uplink_bytes() / total_rounds,
        "uplink_updates": run.uplink_updates,
        # 1 == no recompile across sampled cohorts after warmup
        "compiled_programs": run._round_fn._cache_size(),
    }
    print("MILLION " + json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
