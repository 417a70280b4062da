#!/usr/bin/env python3
"""Where a cell's rounds spend their time, from one traced run:

    python3 bench/phases.py --workload alexnet.sync-stragglers --seed 7 \
        [--seconds 3] [--out FILE]

Runs the cell as ``bench/run.py --trace 1`` does (TPU only) and prints one
JSON object, also written to ``--out``:

* ``metrics``: the result line's per-layer metrics, ``correct``, ``window``;
* ``host_ms``: per round, the host self time of each program span from
  ``repro.obs``'s ring, placed on the trace's clock;
* ``idle_ms``: per round, the device's idle time by the innermost program
  span it falls in, and ``idle_under_spans``, the share of idle time under
  some program span;
* ``scopes_ms``: per round, the device self time of the round program's
  operations (those inside its module executions) by named scope, with
  ``(no scope)`` for the rest and ``unscoped_top``, its largest operations;
* ``kernel_events``: the device events ``masked_matmul_roofline`` counts,
  by name, with their ``tf_op`` paths.
"""
import argparse
import bisect
import collections
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import program_trace as PT  # noqa: E402
from bench import run  # noqa: E402
from bench import trace_reduce as T  # noqa: E402

SCOPES = ("fl_straggler_train", "fl_capable_train", "fl_local_train",
          "fl_aggregate")
ROUND_PROGRAMS = ("jit_round_fn", "jit_round_body")
NO_SPAN = "(no bench span)"


def _kernel_match():
    path = os.path.join(ROOT, "bench", "metrics",
                        "masked_matmul_roofline.py")
    spec = importlib.util.spec_from_file_location("roofline_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod._is_kernel


def _per_round(counter, ctx, planes=1) -> dict:
    k = planes * ctx["rounds"]
    return {n: v * 1e3 / k for n, v in counter.most_common()}


def idle_by_span(ctx) -> dict:
    """Device idle seconds per round by the innermost program span it falls
    in (``NO_SPAN``: under none), averaged over the chips."""
    pt = PT.program_trace(ctx)
    tr, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    out = collections.Counter()
    for d in tr.devices.values():
        out.update(T.attribute(T.gaps(d["ops"], lo, hi),
                               pt.spans if pt else []))
    k = len(tr.devices) * ctx["rounds"]
    return {n: v / k for n, v in out.most_common()}


def host_phases(ctx) -> dict:
    pt, lo, hi = PT.program_trace(ctx), ctx["lo"], ctx["hi"]
    own = collections.Counter()
    for n, t, s, e in T.self_times(pt.spans if pt else []):
        own[n] += t * PT.inside(s, e, lo, hi)
    idle = {n: v * 1e3 for n, v in idle_by_span(ctx).items()}
    total = sum(idle.values())
    return {"host_ms": _per_round(own, ctx), "idle_ms": idle,
            "idle_under_spans": (total - idle.get(NO_SPAN, 0.0)) / total
            if total else None}


def device_scopes(ctx) -> dict:
    """Self time of the round program's operations by scope; an operation
    belongs to the round program when a round-program module execution on
    its chip encloses its start."""
    pt, tr, lo, hi = PT.program_trace(ctx), ctx["trace"], ctx["lo"], \
        ctx["hi"]
    is_kernel = _kernel_match()
    scopes, unscoped = collections.Counter(), collections.Counter()
    kernels = {}
    for plane, ops in pt.ops.items():
        mods = sorted((s, e) for n, s, e in tr.devices[plane]["modules"]
                      if n.startswith(ROUND_PROGRAMS))
        starts = [s for s, _ in mods]
        by_time = {(s, e): op for op, _, s, e in ops}
        for op, t, s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                continue
            w = t * PT.inside(s, e, lo, hi)
            scope = next((c for c in SCOPES if PT.in_scope(op, c)),
                         "(no scope)")
            scopes[scope] += w
            if scope == "(no scope)":
                unscoped[op] += w
        for n, s, e in tr.devices[plane]["ops"]:
            if not is_kernel(n) or e <= lo or s >= hi:
                continue
            k = kernels.setdefault(T.short_name(n),
                                   {"count": 0, "ms_per_round": 0.0,
                                    "tf_op": by_time.get((s, e), "")})
            k["count"] += 1
            k["ms_per_round"] += (min(e, hi) - max(s, lo)) * 1e-6 \
                / ctx["rounds"] / len(pt.ops)
    planes = len(pt.ops)
    return {"scopes_ms": _per_round(scopes, ctx, planes),
            "unscoped_top": list(_per_round(unscoped, ctx, planes)
                                 .items())[:10],
            "kernel_events": kernels}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=run.TRACE_SECONDS)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    bench = run.load_benchmark()
    workload = next(w for w in bench["workloads"]
                    if w["name"] == args.workload)
    run._use_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < workload["chips"]:
        print(f"bench/phases.py: {args.workload} needs "
              f"{workload['chips']} TPU chip(s)", file=sys.stderr)
        return 2

    # keep the context the per-layer readers are given
    captured, load_reader = {}, run._load_reader

    def spy(name):
        read = load_reader(name)

        def kept(ctx):
            captured["ctx"] = ctx
            return read(ctx)
        return kept

    run._load_reader = spy
    result = run.run_cell(bench, workload, args.seed, args.seconds, True)
    ctx = captured["ctx"]
    out = {"workload": args.workload, "seed": args.seed,
           "correct": result["correct"], "window": result["window"],
           "rounds": ctx["rounds"],
           "metrics": {k: v["value"] for k, v in result["metrics"].items()},
           **host_phases(ctx), **device_scopes(ctx)}
    text = json.dumps(out, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
