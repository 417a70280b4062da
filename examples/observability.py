"""Produce and read one telemetry run log (the repro.obs layer).

Every engine already does its accounting through a ``repro.obs.Recorder``
(counters, byte gauges, device-scalar accumulators) — that layer is free
and always on.  Arming telemetry (``REPRO_OBS=on``, or in-process as
below) additionally streams dual-clock events: every span/round/bucket
event carries the engine's SIMULATED clock (deterministic — fixed-seed
streams are identical across engines) next to the host WALL clock (what
the instrumented sections really cost).  ``flush()`` writes the JSONL
event log + run manifest the ``repro.obs`` CLI consumes:

  PYTHONPATH=src python examples/observability.py --out obs_demo

  # the same report this script prints, straight from the CLI:
  PYTHONPATH=src python -m repro.obs report obs_demo

  # regression-gate one run log against another (nonzero on regression):
  PYTHONPATH=src python -m repro.obs diff obs_demo other_run

Capture a ``jax.profiler`` trace around one chosen round with
``REPRO_OBS_PROFILE=<round>`` (or ``profile_round=`` on the Recorder).

Armed or not, every span also lands in an in-process ring that any code in
the process reads with ``repro.obs.recent_spans()``: after a slow or
stalled round, the last round's ``fl.round`` children say which phase took
the time (``fl.sample``, ``fl.stack``, ``fl.dispatch``, ``fl.writeback``,
``fl.adapt``, ``fl.evaluate``).  The script prints them last.  Their
``start_ns``/``end_ns`` are ``time.time_ns()`` stamps, the clock of a
``jax.profiler`` trace's ``profile_start_time``.
"""
import argparse

from repro.configs import CNNS, HeliosConfig, reduced
from repro.data.federated import partition_noniid
from repro.data.synthetic import class_gaussian_images
from repro.federated import BatchedFLRun, make_fleet, setup_clients
from repro.obs import Recorder, load_events, recent_spans, render, summarize


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="lenet",
                    choices=["lenet", "alexnet", "resnet18"])
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--out", default="obs_demo",
                    help="run-log directory (events.jsonl + manifest.json)")
    ap.add_argument("--profile-round", type=int, default=None,
                    help="capture a jax.profiler trace around this round")
    args = ap.parse_args()

    cfg = reduced(CNNS[args.model])
    imgs, labels = class_gaussian_images(
        1024, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=0)
    ti, tl = class_gaussian_images(
        128, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=99)
    n = args.clients
    hcfg = HeliosConfig()
    parts = partition_noniid(labels, n, shards_per_client=4, seed=0)
    clients = setup_clients(make_fleet(n - n // 2, n // 2), parts, hcfg)

    # an explicitly-armed recorder overrides REPRO_OBS for this run only
    rec = Recorder(armed=True, profile_round=args.profile_round)
    run = BatchedFLRun(cfg, hcfg, "helios", clients,
                       {"images": imgs, "labels": labels},
                       {"images": ti, "labels": tl},
                       local_steps=1, batch_size=16, lr=0.05, seed=0,
                       recorder=rec)
    run.run_sync(args.rounds)

    out = rec.flush(args.out)
    print(f"== run log: {out['events']} ==\n")
    events = load_events(args.out)
    print(render(events))
    summ = summarize(events)
    print(f"\n== summary: {summ['rounds']} rounds, "
          f"final {summ.get('metric_name')}={summ.get('final_metric'):.3f}, "
          f"uplink {summ['uplink_mb']:.2f} MB / "
          f"downlink {summ['downlink_mb']:.2f} MB ==")
    print("rerun with --profile-round 1 (or REPRO_OBS_PROFILE=1) to drop "
          "a jax.profiler trace next to the log")
    # the span ring: where the last round's host time went, by phase
    phases = [s for s in recent_spans()
              if s.round == args.rounds - 1 and s.parent == "fl.round"]
    print("last round by phase: " + ", ".join(
        f"{s.name} {(s.end_ns - s.start_ns) * 1e-6:.1f} ms" for s in phases))


if __name__ == "__main__":
    main()
