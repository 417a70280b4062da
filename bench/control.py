#!/usr/bin/env python3
"""Readings that the limits of ``compare.py`` are set from.

    python bench/control.py --workload alexnet.sync-stragglers \
        --variant program --seeds 1 2 3 ...

For each seed it builds the cell's inputs and compares, by the numbers of
``compare.py``, the plain float32 reference with one of:

* ``program``: the engine under test, driven through its set-up rounds as
  ``run.py`` drives it (the sound runs: the lower reading of each limit);
* ``bf16``: the control, the reference computed in bfloat16 in the
  engine's place;
* ``half_batch``: the reference with half of every batch left out, the
  mean taken over the rest;
* ``one_chip``: the reference aggregating only the first quarter of the
  cohort, as a round whose exchange between four chips is left out.

A state left unchanged reads 1 by the norm gaps and needs no run.  One JSON
line per seed goes to standard output.  Like ``run.py`` it runs on a TPU
only (the tests call ``reading`` on the CPU).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

VARIANTS = {"bf16": {"dtype": "bfloat16", "precision": None},
            "half_batch": {"batch_keep": 0.5},
            "one_chip": {"agg_keep": 0.25}}


def reading(workload: dict, seed: int, variant: str, cfg=None,
            traffic=None) -> dict:
    import jax.numpy as jnp
    from bench import compare, run
    from bench.reference import Reference
    from bench.world import make_world

    world = make_world(workload, seed, cfg, traffic)
    rounds = world.traffic["check_rounds"]
    before = compare.host_leaves(world.weights)
    if variant == "program":
        engine, before, prog = run.set_up(world)
        del engine
    else:
        kw = dict(VARIANTS[variant])
        if "dtype" in kw:
            kw["dtype"] = getattr(jnp, kw["dtype"])
        prog = compare.replay(Reference(world, **kw), rounds)
    ref = compare.replay(Reference(world), rounds)
    return compare.readings(prog, ref, before, world.units)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", required=True,
                    choices=["program"] + sorted(VARIANTS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import run
    bench = run.load_benchmark()
    workload = next(w for w in bench["workloads"]
                    if w["name"] == args.workload)
    run._use_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < workload["chips"]:
        print(f"bench/control.py needs {workload['chips']} TPU chip(s)",
              file=sys.stderr)
        return 2
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "variant": args.variant,
                          "seed": seed, "device": devs[0].device_kind,
                          **reading(workload, seed, args.variant)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
