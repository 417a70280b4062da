#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python bench/run.py --workload alexnet.sync-stragglers --seed 7 \
        --seconds 30 --trace 0

A cell is one ``workloads`` entry of ``BENCHMARK.json``: a configuration
(``bench/configs/``) under a traffic mix (``bench/traffic/``).  The run
builds the cell's data, fleet and weights from ``--seed``, builds the engine
under test, and drives its first rounds through ``run_sync`` with
evaluation every round (set-up: this compiles, or reads JAX's compilation
cache at ``<checkout>/.jax_cache``).  Then it measures federated rounds for
``--seconds`` seconds, ending on a round boundary.  Once the window has
closed and the engine is freed, the plain reference replays the set-up
rounds and ``compare.py`` decides ``correct``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces a
window of at most ``TRACE_SECONDS`` with the JAX profiler and reports the
cell's per-layer metrics, each read by ``bench/metrics/<name>.py``, with the
device's busy time and a breakdown.  It runs on a TPU only: with another
platform, or fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
CACHE = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")
#: longest traced window: the per-layer metrics are per round, and a trace
#: (about 17 MB per AlexNet round) of a few seconds keeps its reading well
#: inside a run's time
TRACE_SECONDS = 3.0

_COMPILE = "/jax/core/compile/backend_compile_duration"


class _WindowClosed(Exception):
    pass


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _use_cache() -> str:
    """Keep JAX's persistent compilation cache inside the checkout, at a
    fixed path, and cache every program (also the small eager ones), so
    only a cell's first run in a checkout compiles.  The TPU runtime's own
    logs, which it would write under /tmp, are turned off."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro import xla_env
    path = xla_env.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _compile_counter() -> collections.Counter:
    from jax import monitoring
    events = collections.Counter()

    def on_duration(event, duration, **kwargs):
        events[event] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    return events


def _masks(run, cids) -> dict:
    """Each listed client's current unit masks, as the engine keeps them
    (copied: the sharded engine updates its host-side rows in place)."""
    import numpy as np
    if not hasattr(run, "client_state"):
        run.sync_client_states()
    out = {}
    for i in cids:
        st = run.client_state(i) if hasattr(run, "client_state") \
            else run.clients[i].helios_state
        out[i] = {k: np.array(v) for k, v in st["masks"].items()}
    return out


def set_up(world):
    """Build the engine and drive the first ``check_rounds`` rounds through
    ``run_sync``; returns the engine and what the comparison reads."""
    from bench.compare import host_leaves
    from bench.world import build_engine
    tr = world.traffic
    run = build_engine(world)
    before = host_leaves(run.global_params)
    prog = {"losses": [], "ratios": [], "masks": {}, "params": []}
    for r in range(tr["check_rounds"]):
        run.run_sync(1, eval_every=tr["eval_every"])
        row = run.history[-1]
        prog["losses"].append(row["loss"])
        prog["ratios"].append(row["ratios"])
        if r == 0:
            strag = [i for i in run.cohort_log[-1] if world.fleet[i][0]]
            prog["masks"] = _masks(run, strag)
            prog["params"].append(host_leaves(run.global_params))
    prog["params"].append(host_leaves(run.global_params))
    return run, before, prog


def window(run, seconds: float, eval_every: int):
    """Run rounds through one ``run_sync`` call until ``seconds`` have
    passed, closing on a round boundary.  Each round is timed at the
    evaluation gate (``_record_round``), where the engine syncs on the
    device.  Returns the gate times, starting with the window's start."""
    record = run._record_round
    stamps = []

    def gate(*args, **kwargs):
        record(*args, **kwargs)
        stamps.append(time.perf_counter())
        if stamps[-1] - stamps[0] >= seconds:
            raise _WindowClosed

    run._record_round = gate
    stamps.append(time.perf_counter())
    try:
        run.run_sync(10 ** 9, eval_every=eval_every)
    except _WindowClosed:
        pass
    finally:
        run._record_round = record
    return stamps


class HostSpans:
    """Host-clock spans around the engine's calls in the traced run: batch
    sampling (whichever of the engine's two samplers it calls), the round's
    dispatch (``_train_cohort``) and the evaluation gate.  They are kept in
    memory and placed on the trace's clock afterwards (``place``), since
    the profiler's own host tracing slows a round by 40-130%."""

    def __init__(self, run):
        self.spans = []
        self._wrap(run, "_sample_batches", "bench.sample")
        self._wrap(run.adapter, "sample_cohort", "bench.sample")
        self._wrap(run, "_train_cohort", "bench.train_cohort")
        self._wrap(run, "_record_round", "bench.record_round")

    def _wrap(self, obj, name, span):
        fn = getattr(obj, name)

        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                self.spans.append((span, t0, time.perf_counter()))

        setattr(obj, name, timed)

    def place(self, trace, stamps) -> None:
        """Put the window and the spans on the trace's clock: the window
        closes when its last round's evaluation has synced, which is where
        the device's last operation ends."""
        hi = trace.last_device_end()
        lo = hi - (stamps[-1] - stamps[0]) * 1e9

        def at(t):
            return hi + (t - stamps[-1]) * 1e9

        trace.spans = [("bench.window", lo, hi)] + [
            (n, at(a), at(b)) for n, a, b in self.spans]


def _peak_bytes(devices) -> int:
    """The fullest chip's peak: the allocator's peak in use plus the peak
    that the runtime reserved for compiled programs' scratch (on a TPU the
    round program's temporaries are in the second)."""
    def peak(d):
        st = d.memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0)) \
            + int(st.get("peak_bytes_reserved", 0))
    return max(peak(d) for d in devices)


def _load_reader(name: str):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_for(bench: dict, name: str) -> list:
    """The per-layer metrics that this cell reports."""
    ends = {m["name"] for m in bench["end_to_end"]
            if name in m.get("workloads", [name])}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name]) and m["moves"] in ends]


def end_to_end_for(bench: dict, name: str) -> list:
    return [m for m in bench["end_to_end"]
            if name in m.get("workloads", [name])]


def run_cell(bench: dict, workload: dict, seed: int, seconds: float,
             trace: bool, cfg=None, traffic=None, limits=None) -> dict:
    """Everything after the platform check; returns the result line.
    ``cfg``, ``traffic`` and ``limits`` default to the cell's files (tests
    pass their own small ones)."""
    import jax
    from bench import compare, flops, trace_reduce
    from bench.reference import Reference
    from bench.world import make_world

    compiles = _compile_counter()
    world = make_world(workload, seed, cfg, traffic)
    tr = world.traffic
    run, before, prog = set_up(world)
    setup_s = time.perf_counter() - T0
    devices = sorted(jax.tree.leaves(run.global_params)[0].sharding
                     .device_set, key=lambda d: d.id)

    metrics, device, breakdown, host = {}, {}, None, None
    c0 = compiles[_COMPILE]
    if not trace:
        stamps = window(run, seconds, tr["eval_every"])
    else:
        host = HostSpans(run)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            stamps = window(run, min(seconds, TRACE_SECONDS),
                            tr["eval_every"])
        finally:
            jax.profiler.stop_trace()
    window_compiles = compiles[_COMPILE] - c0
    n_rounds = len(stamps) - 1
    times = [b - a for a, b in zip(stamps, stamps[1:])]
    losses = [row["loss"] for row in run.history[-n_rounds:]]
    failed = sum(1 for x in losses if not math.isfinite(x))
    device["memory_peak_bytes"] = _peak_bytes(devices)

    if trace:
        tdata = trace_reduce.load(TRACE_DIR)
        host.place(tdata, stamps)
        lo, hi = tdata.window()
        busy = trace_reduce.mean_busy(tdata, lo, hi)
        cohorts = run.cohort_log[-n_rounds:]
        strag = sorted({i for c in cohorts for i in c if world.fleet[i][0]})
        ctx = {"trace": tdata, "lo": lo, "hi": hi, "busy_s": busy,
               "rounds": n_rounds, "chips": len(devices),
               "compiles": window_compiles,
               "peaks": flops.peaks(devices[0].device_kind),
               "cost": flops.RoundCost(world, cohorts, _masks(run, strag))}
        for m in per_layer_for(bench, workload["name"]):
            value = _load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=busy, window_s=(hi - lo) * 1e-9)
        breakdown = trace_reduce.breakdown(tdata, lo, hi)
    else:
        e2e = {"round_s": (stamps[-1] - stamps[0]) / n_rounds,
               "round_p90_s": statistics.quantiles(
                   times, n=10, method="inclusive")[-1]
               if len(times) > 1 else times[0],
               "setup_s": setup_s}
        for m in end_to_end_for(bench, workload["name"]):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    del run, host                      # the spans' wrappers hold the engine
    gc.collect()
    ref = compare.replay(Reference(world), tr["check_rounds"])
    if limits is None:
        with open(os.path.join(ROOT, "bench", "limits",
                               workload["name"] + ".json")) as f:
            limits = json.load(f)
    checks = compare.judge(compare.readings(prog, ref, before, world.units),
                           limits)
    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": n_rounds, "failed": failed, "metrics": metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices), **device}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    med = statistics.median(times)
    result["window"] = (f"{n_rounds} rounds; seconds per round min "
                        f"{min(times):.4f} median {med:.4f} max "
                        f"{max(times):.4f}; over 1.5x the median: "
                        f"{sum(t > 1.5 * med for t in times)}")
    result["checks"] = {n: {"value": c["value"], "limit": c["limit"]}
                        for n, c in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"unknown workload {args.workload!r}; BENCHMARK.json has "
                 f"{sorted(cells)}")
    workload = cells[args.workload]
    _use_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < workload["chips"]:
        print(f"bench/run.py: {args.workload} needs {workload['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform!r} "
              f"device(s). There is no fallback.", file=sys.stderr)
        return 2
    result = run_cell(bench, workload, args.seed, args.seconds,
                      bool(args.trace))
    print(f"window: {result.pop('window')}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
