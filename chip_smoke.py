"""Bring-up smoke of the synchronous Helios round on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # client-sharded round over four chips

One chip: the paper's CIFAR-scale AlexNet at its published widths (no
``reduced()``) trains three synchronous Helios rounds through
``BatchedFLRun`` with the Pallas soft-training kernels, four capable clients
and four Table I stragglers, then the same seed again on the reference
kernels.  The script checks that

* JAX runs on a TPU (there is no CPU fallback), the masked dense layers
  compile to Mosaic kernels (``tpu_custom_call``), and at full width their
  forward and backward match the reference within ``KERNEL_RTOL``;
* every round metric and the final parameters are finite;
* each straggler's selected fraction (``ratios``) is the block-quantized
  share of its volume P, and every capable client trains the full model;
* nothing compiles after the first round (with ``--chips 4``, in the
  shape-stable ``ShardedFLRun``);
* the Pallas and reference trajectories agree within ``TRAJ_RTOL`` and
  ``LOSS_RTOL``.

``--chips 4`` runs only ``ShardedFLRun`` over a four-chip client mesh and
``BatchedFLRun`` on one chip as its reference, with the same seed, world
and participation, and compares their trajectories.

Lines tagged ``[info]`` are informative bring-up observations, not
benchmark metrics.  The last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax import monitoring

from repro import xla_env
from repro.configs import CNNS, HeliosConfig
from repro.data.federated import partition_iid
from repro.data.synthetic import class_gaussian_images
from repro.federated import BatchedFLRun, make_fleet, setup_clients
from repro.federated.runtime import ShardedFLRun
from repro.kernels import ops

SEED = 0
ROUNDS = 3
MASK_BLOCK = 128
BATCH = 32
LOCAL_STEPS = 2
LR = 0.02
#: Trajectories run under ``jax.default_matmul_precision("highest")``: a
#: TPU f32 matmul otherwise takes one bf16 pass, which the Pallas
#: ``jnp.dot`` and XLA's dot may round differently.  At f32 the substrates
#: still differ in summation order, and a pre-activation that lands within
#: that rounding of zero flips its ReLU derivative: the unit's whole
#: gradient column changes.  Those flips accumulate over rounds, so two
#: correct substrates drift apart: on a TPU v5e, by 0.0068 (pallas vs
#: reference, one chip) and 0.0045 (sharded over four chips vs batched) of
#: the three rounds' parameter displacement, in norm.  The bound sits about
#: 7x above those and well below what a cross-chip aggregation fault gives
#: in the four-chip phase on four CPU host devices: 0.23 with one shard's
#: rows left out of the psum, 0.44 with half the cohort dropped (a sound
#: run there drifts 0.033).  The kernels' own numerics are held to
#: ``KERNEL_RTOL``.
TRAJ_RTOL = 0.05
#: per-round mean training loss, relative: 2.0e-5 and 8.8e-7 on a TPU v5e;
#: 2.2e-4 sound and 6.1e-3 / 9.1e-3 with those faults on the CPU
LOSS_RTOL = 5e-4
#: one masked dense layer, forward and VJP, max gap over the largest
#: reference entry: f32 summation over K <= 4096 terms differs by about
#: sqrt(K) * 2**-24 (~4e-6); a single bf16 pass would differ by ~2**-9
KERNEL_RTOL = 1e-4

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _count_events() -> collections.Counter:
    """Count compiles (and persistent-cache hits) for the whole process:
    every executable JAX obtains, compiled or read from the cache, passes
    through ``_BACKEND_COMPILE``."""
    events = collections.Counter()

    def on_duration(event, duration, **kwargs):
        events[event] += 1
        events[event + ":secs"] += duration

    def on_event(event, **kwargs):
        events[event] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return events


def _info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


def _require(ok, *what) -> None:
    """A failed check ends the run (independent of ``python -O``)."""
    if not ok:
        raise RuntimeError("chip_smoke check failed: "
                           + " ".join(str(w) for w in what))


def _require_tpu(chips: int) -> jax.Device:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s)). There is no "
                 f"CPU fallback.")
    if len(devs) < chips:
        sys.exit(f"chip_smoke.py --chips {chips}: JAX found only "
                 f"{len(devs)} TPU device(s)")
    return devs[0]


def _check_kernels() -> None:
    """AlexNet's two masked dense layers at full width, half their blocks
    dead: the Pallas path compiles to a Mosaic kernel (at the default
    matmul precision users run), and its forward and VJP match the
    reference at f32 precision, with dead columns exactly zero."""
    _require(not ops._interpret(),
             "Pallas kernels would run in interpret mode")
    key = jax.random.PRNGKey(SEED)
    for name, k, n in (("fc0", 4096, 1024), ("fc1", 1024, 512)):
        kx, kw, kd = jax.random.split(jax.random.fold_in(key, n), 3)
        x = jax.random.normal(kx, (BATCH, k), jnp.float32)
        w = jax.random.normal(kw, (k, n), jnp.float32) * k ** -0.5
        dy = jax.random.normal(kd, (BATCH, n), jnp.float32)
        mask = jnp.repeat(jnp.arange(n // MASK_BLOCK) % 2 == 0,
                          MASK_BLOCK).astype(jnp.float32)

        def fwd_bwd(impl):
            def fn(x, w, dy):
                y, vjp = jax.vjp(lambda x, w: ops.masked_dense(
                    x, w, mask, impl=impl, block_n=MASK_BLOCK), x, w)
                return (y,) + vjp(dy)
            return jax.jit(fn)

        text = fwd_bwd(ops.PALLAS).lower(x, w, dy).compile().as_text()
        _require(text.count("tpu_custom_call") >= 3, f"{name}: no kernels")
        with jax.default_matmul_precision("highest"):
            got = fwd_bwd(ops.PALLAS)(x, w, dy)
            want = fwd_bwd(ops.REFERENCE)(x, w, dy)
        dead = mask == 0
        for part, g, r in zip(("y", "dx", "dw"), got, want):
            gap = float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
            _require(gap <= KERNEL_RTOL, name, part, gap)
            if part != "dx":
                _require(not bool(jnp.any(g[:, dead])), name, part, "dead")
            _info(f"masked_dense {name} ({BATCH}x{k} . {k}x{n}) {part}: "
                  f"tpu_custom_call; max gap / max |ref| {gap:.3e}")


def _world(n_clients: int):
    cfg = CNNS["alexnet"]                      # published widths
    imgs, labels = class_gaussian_images(
        256 * n_clients, cfg.image_size, cfg.in_channels, cfg.num_classes,
        seed=SEED)
    ti, tl = class_gaussian_images(
        512, cfg.image_size, cfg.in_channels, cfg.num_classes,
        seed=SEED + 99)
    parts = partition_iid(len(labels), n_clients, seed=SEED)
    return (cfg, {"images": imgs, "labels": labels},
            {"images": ti, "labels": tl}, parts)


def _engine(cls, world, kernels, n_capable, n_straggler, **kw):
    cfg, train, test, parts = world
    hcfg = HeliosConfig(mask_block=MASK_BLOCK)
    clients = setup_clients(make_fleet(n_capable, n_straggler), parts, hcfg)
    return cls(cfg, hcfg, "helios", clients, train, test,
               batch_size=BATCH, local_steps=LOCAL_STEPS, lr=LR, seed=SEED,
               kernels=kernels, **kw)


def _expected_ratio(schema, volume: float) -> float:
    """Selected fraction of Eq. 2 at volume P: unit types at least four
    blocks wide select whole blocks, the rest single units (the
    arithmetic of ``core.selection.select_masks``, in float32)."""
    p = np.float32(volume)
    sel = tot = 0
    for rows, n in schema.values():
        if n >= 4 * MASK_BLOCK:
            nb = -(-n // MASK_BLOCK)
            k = min(int(np.clip(np.round(p * np.float32(nb)), 1, nb))
                    * MASK_BLOCK, n)
        else:
            k = int(np.clip(np.round(p * np.float32(n)), 1, n))
        sel += rows * k
        tot += rows * n
    return sel / tot


def _trajectory(run, events) -> dict:
    """Train ``ROUNDS`` rounds one at a time; time them, record each
    round's starting volumes, and count compiles after the first."""
    vols, secs = [], []
    start = _flat(run.global_params)
    for r in range(ROUNDS):
        vols.append([c.volume for c in run.clients])
        t0 = time.perf_counter()
        run.run_sync(1)
        jax.block_until_ready(run.global_params)
        secs.append(time.perf_counter() - t0)
        if r == 0:
            compiles0 = events[_BACKEND_COMPILE]
    return {"run": run, "hist": run.history, "vols": vols, "secs": secs,
            "start": start,
            "late_compiles": events[_BACKEND_COMPILE] - compiles0}


def _check_trajectory(tag: str, tr: dict, shape_stable: bool = True) -> None:
    """``shape_stable``: the engine runs one program shape every round, so
    nothing may compile after round 1 (BatchedFLRun under partial
    participation compiles one program per cohort straggler count)."""
    run, hist = tr["run"], tr["hist"]
    metric = run.adapter.metric_name
    _require(len(hist) == len(tr["vols"]), tag, len(hist))
    for r, (row, vols) in enumerate(zip(hist, tr["vols"])):
        _require(math.isfinite(row["loss"]) and math.isfinite(row[metric]),
                 tag, r, row["loss"], row[metric])
        cohort = run.cohort_log[r]
        for ratio, i in zip(row["ratios"], cohort):
            c = run.clients[i]
            if not c.is_straggler:
                _require(ratio == 1.0, tag, r, i, ratio)
                continue
            want = _expected_ratio(run.adapter.schema, vols[i])
            _require(ratio < 1.0 and abs(ratio - want) <= 1e-6,
                     tag, r, i, vols[i], ratio, want)
    for leaf in jax.tree.leaves(run.global_params):
        _require(bool(jnp.all(jnp.isfinite(leaf))), tag, "non-finite params")
    _require(tr["late_compiles"] == 0 or not shape_stable,
             tag, f"{tr['late_compiles']} compiles after round 1")
    s = tr["secs"]
    _info(f"{tag}: round 1 (compile + run) {s[0]:.3f} s; compiles after "
          f"round 1: {tr['late_compiles']}; after warm-up "
          f"{np.mean(s[1:]):.4f} s/round; {metric} per round "
          f"{[round(h[metric], 4) for h in hist]}; straggler ratios "
          f"{[round(x, 4) for x in hist[-1]['ratios']]}")


def _flat(params) -> jax.Array:
    return jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(params)])


def _compare(a: dict, b: dict, tag: str) -> None:
    """Trajectory ``a`` against reference ``b``: same cohorts and selected
    fractions every round, final parameters within ``TRAJ_RTOL`` of the
    distance ``b`` travelled, per-round losses within ``LOSS_RTOL``."""
    ra, rb = a["run"], b["run"]
    _require(ra.cohort_log == rb.cohort_log, tag, "cohorts differ")
    for x, y in zip(a["hist"], b["hist"]):
        _require(np.allclose(x["ratios"], y["ratios"], rtol=0, atol=1e-6),
                 tag, x["ratios"], y["ratios"])
    pa, pb = _flat(ra.global_params), _flat(rb.global_params)
    travelled = float(jnp.linalg.norm(pb - b["start"]))
    drift = float(jnp.linalg.norm(pa - pb)) / travelled
    loss_gap = max(abs(x["loss"] - y["loss"]) / abs(y["loss"])
                   for x, y in zip(a["hist"], b["hist"]))
    _info(f"{tag}: parameter drift {drift:.4f} of the distance travelled "
          f"{travelled:.4f} (tolerance {TRAJ_RTOL:g}); max |param gap| "
          f"{float(jnp.max(jnp.abs(pa - pb))):.3e}; max relative loss gap "
          f"{loss_gap:.3e} (tolerance {LOSS_RTOL:g})")
    _require(drift <= TRAJ_RTOL, tag, "drift", drift)
    _require(loss_gap <= LOSS_RTOL, tag, "loss gap", loss_gap)


def one_chip(events) -> int:
    _check_kernels()
    world = _world(8)
    trs = {}
    with jax.default_matmul_precision("highest"):
        for kernels in (ops.PALLAS, ops.REFERENCE):
            run = _engine(BatchedFLRun, world, kernels, 4, 4)
            trs[kernels] = _trajectory(run, events)
            _check_trajectory(f"alexnet/{kernels}", trs[kernels])
    _compare(trs[ops.PALLAS], trs[ops.REFERENCE], "pallas vs reference")
    return 1


def four_chips(events) -> int:
    """ShardedFLRun over four chips against BatchedFLRun on one: sixteen
    clients (half stragglers), eight sampled per round."""
    world = _world(16)
    trs = {}
    with jax.default_matmul_precision("highest"):
        for name, cls in (("sharded", ShardedFLRun),
                          ("batched", BatchedFLRun)):
            run = _engine(cls, world, ops.PALLAS, 8, 8, participation=8)
            trs[name] = _trajectory(run, events)
            _check_trajectory(f"alexnet/{name}", trs[name],
                              shape_stable=cls is ShardedFLRun)
    shards = trs["sharded"]["run"]._mesh.devices.size
    _require(shards == 4, f"client mesh has {shards} shard(s), not 4")
    _compare(trs["sharded"], trs["batched"], "sharded (4 chips) vs batched")
    return shards


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    cache = xla_env.use_compile_cache()
    events = _count_events()
    dev = _require_tpu(args.chips)
    _info(f"device {dev.device_kind} x{len(jax.devices())}; compile cache "
          f"{cache}")
    t0 = time.perf_counter()
    phase = four_chips if args.chips == 4 else one_chip
    count = phase(events)
    _info(f"total {time.perf_counter() - t0:.1f} s; compiles "
          f"{events[_BACKEND_COMPILE]} "
          f"({events[_BACKEND_COMPILE + ':secs']:.1f} s), persistent-cache "
          f"hits {events[_CACHE_HIT]}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
