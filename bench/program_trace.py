"""The program's own spans and named device scopes, on a trace's clock.

The program keeps its spans in ``repro.obs``'s ring (``recent_spans()``),
stamped with ``time.time_ns()``: the host clock the JAX profiler stamps its
events on.  A trace's ``Task Environment`` plane holds
``profile_start_time`` on that clock, and every event in the trace is timed
from it, so a span lies at ``start_ns - profile_start_time`` on the trace:
nothing is guessed.  The round programs name their parts with
``jax.named_scope`` (``fl_straggler_train``, ``fl_capable_train``,
``fl_local_train``, ``fl_aggregate``); on the chip each device operation's
``tf_op`` metadata holds the scope path (``jit(round_fn)/fl_aggregate/...``).
``ProfileData`` does not expose that metadata, so the ``.xplane.pb`` is read
with the XPlane protobuf module, loaded by its file path (TensorFlow itself
is not imported).

The readers in ``bench/metrics/`` call :func:`span_ms` and :func:`scope_ms`:
self time (a span's or an operation's time less that of what it encloses)
per round, inside the window ``ctx["lo"]`` .. ``ctx["hi"]``.  Where the
program has no such span or scope, they return ``None``.
"""
from __future__ import annotations

import dataclasses
import glob
import importlib.util
import os
import sys

from bench import trace_reduce as T

ENVIRONMENT_PLANE = "Task Environment"
_XPLANE = "tensorflow.tsl.profiler.protobuf.xplane_pb2"


@dataclasses.dataclass
class ProgramTrace:
    #: per device plane: [(tf_op, self seconds, start, end)], start order
    ops: dict
    #: the program's spans [(name, start, end)] on the trace's clock
    spans: list


def xplane_pb2():
    """The XPlane protobuf module, or None where the image has none."""
    if _XPLANE in sys.modules:
        return sys.modules[_XPLANE]
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    if not os.path.exists(path):
        return None
    mod_spec = importlib.util.spec_from_file_location(_XPLANE, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    sys.modules[_XPLANE] = mod
    return mod


def read_xspace(path: str):
    pb = xplane_pb2()
    if pb is None:
        return None
    xs = pb.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def _stat_names(plane) -> dict:
    return {k: v.name for k, v in plane.stat_metadata.items()}


def profile_start_ns(xs) -> int | None:
    for plane in xs.planes:
        if plane.name != ENVIRONMENT_PLANE:
            continue
        names = _stat_names(plane)
        for st in plane.stats:
            if names.get(st.metadata_id) == "profile_start_time":
                return int(st.uint64_value)
    return None


def device_ops(xs) -> dict:
    """Per device plane, the ``XLA Ops`` events as (tf_op, start, end), in
    nanoseconds from the trace's start, truncated to whole nanoseconds as
    ``ProfileData`` gives them (so ``trace_reduce``'s events match these
    exactly)."""
    out = {}
    for plane in xs.planes:
        if not plane.name.startswith(T.DEVICE_PREFIX):
            continue
        names = _stat_names(plane)
        tf_op = {k for k, n in names.items() if n == "tf_op"}
        meta = {}
        for mid, md in plane.event_metadata.items():
            meta[mid] = ""
            for st in md.stats:
                if st.metadata_id in tf_op:
                    meta[mid] = st.str_value or names.get(st.ref_value, "")
        ops = []
        for line in plane.lines:
            if line.name != T.OPS_LINE:
                continue
            for e in line.events:
                s = line.timestamp_ns + e.offset_ps // 1000
                ops.append((meta.get(e.metadata_id, ""), float(s),
                            float(s + e.duration_ps // 1000)))
        out[plane.name] = ops
    return out


def placed_spans(start_ns: int | None) -> list:
    """The program's spans from ``repro.obs``'s ring, on the trace's clock;
    none where the program keeps no ring or the trace no start time."""
    from repro import obs
    recent = getattr(obs, "recent_spans", None)
    if recent is None or start_ns is None:
        return []
    return [(s.name, float(s.start_ns - start_ns), float(s.end_ns - start_ns))
            for s in recent()]


def load(directory: str) -> ProgramTrace | None:
    """The newest ``.xplane.pb`` under ``directory``, with the spans that
    the program's ring holds now."""
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    xs = read_xspace(max(files, key=os.path.getmtime)) if files else None
    if xs is None:
        return None
    return ProgramTrace({p: T.self_times(ops)
                         for p, ops in device_ops(xs).items()},
                        placed_spans(profile_start_ns(xs)))


def program_trace(ctx) -> ProgramTrace | None:
    """The traced run's ``ProgramTrace``, read once per run into ``ctx``."""
    if "program_trace" not in ctx:
        from bench.run import TRACE_DIR
        ctx["program_trace"] = load(TRACE_DIR)
    return ctx["program_trace"]


def inside(s: float, e: float, lo: float, hi: float) -> float:
    """The share of [s, e) inside [lo, hi)."""
    if e <= s:
        return float(lo <= s < hi)
    return max(0.0, min(e, hi) - max(s, lo)) / (e - s)


def in_scope(tf_op: str, scope: str) -> bool:
    return scope in tf_op.split("/")


def scope_ms(ctx, scope: str) -> float | None:
    """Device milliseconds per round of the operations under the named
    scope (self time), averaged over the device planes."""
    pt = program_trace(ctx)
    if pt is None or not pt.ops:
        return None
    tot, found = 0.0, False
    for ops in pt.ops.values():
        for name, t, s, e in ops:
            if in_scope(name, scope):
                found = True
                tot += t * inside(s, e, ctx["lo"], ctx["hi"])
    if not found:
        return None
    return tot / len(pt.ops) * 1e3 / ctx["rounds"]


def span_ms(ctx, name: str) -> float | None:
    """Host milliseconds per round in the program's ``name`` spans (self
    time), inside the window."""
    pt = program_trace(ctx)
    if pt is None:
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    own = [t * inside(s, e, lo, hi) for n, t, s, e in T.self_times(pt.spans)
           if n == name and e > lo and s < hi]
    if not own:
        return None
    return sum(own) * 1e3 / ctx["rounds"]

