"""Reduce a JAX profiler trace to the benchmark's per-layer numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device planes
(``/device:TPU:<n>``) carry a line of XLA operations and a line of XLA
module (compiled program) executions.  The ``bench.*`` host spans come
from the trace's host planes where the profiler recorded them
(``jax.profiler.TraceAnnotation``), or are placed on its clock by the
caller (``run.HostSpans``).  All times are nanoseconds on the profiler's
clock.

* busy time: the union of a device's operation intervals inside the window;
* idle gaps: the rest of the window, each piece named by the innermost
  ``bench.*`` host span it falls in;
* kernel or collective time: summed device durations of the matching
  operations.

An operation event's name is its HLO instruction (``%name = shape op(...)``);
a ``while`` loop's event encloses the events of its body, so per-operation
time is self time: an event's duration less that of the events it
encloses.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    #: per device plane: {"ops": [(name, start, end)], "modules": [...]}
    devices: dict
    #: host spans [(name, start, end)] named ``bench.*``
    spans: list

    def last_device_end(self) -> float:
        return max(e for d in self.devices.values() for _, _, e in d["ops"])

    def window(self) -> tuple:
        """(start, end) of the measured window's ``bench.window`` span."""
        w = [s for s in self.spans if s[0] == SPAN_PREFIX + "window"]
        if not w:
            raise ValueError("the trace has no bench.window span")
        return w[0][1], w[0][2]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def short_name(op: str) -> str:
    """``%fusion.3 = f32[...] fusion(...), kind=kLoop, ...`` -> ``%fusion.3``,
    with the custom-call target where there is one."""
    name = op.split(" = ", 1)[0]
    if 'custom_call_target="' in op:
        name += " " + op.split('custom_call_target="', 1)[1].split('"', 1)[0]
    return name


def self_times(events) -> list:
    """(name, self seconds, start, end) of each event, in start order."""
    order = sorted(events, key=lambda x: (x[1], -x[2]))
    own = [e - s for _, s, e in order]
    stack = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(n, t * 1e-9, s, e) for (n, s, e), t in zip(order, own)]


def from_profile(prof) -> Trace:
    """From a ``jax.profiler.ProfileData``."""
    devices, spans = {}, []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {l.name: _events(l) for l in plane.lines}
            devices[plane.name] = {"ops": lines.get(OPS_LINE, []),
                                   "modules": lines.get(MODULES_LINE, [])}
        else:
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e[0].startswith(SPAN_PREFIX)]
    return Trace(devices, sorted(spans, key=lambda s: s[1]))


def load(directory: str) -> Trace:
    """The newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return from_profile(ProfileData.from_file(max(files,
                                                  key=os.path.getmtime)))


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) pieces of ``intervals`` clipped to [lo, hi)."""
    out = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list:
    """Idle pieces of [lo, hi): where no interval runs."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def mean_busy(trace: Trace, lo: float, hi: float) -> float:
    """Busy seconds inside the window, averaged over the device planes."""
    return sum(busy_ns(d["ops"], lo, hi) for d in trace.devices.values()) \
        / len(trace.devices) * 1e-9


def attribute(pieces, spans) -> dict:
    """Seconds of each [start, end) piece under the innermost span, by span
    name; time under no ``bench.*`` span goes to ``(no bench span)``."""
    out = collections.Counter()
    for s, e in pieces:
        cuts = sorted({s, e} | {t for _, a, b in spans for t in (a, b)
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inside = [x for x in spans if x[1] <= mid < x[2]]
            name = min(inside, key=lambda x: x[2] - x[1])[0] if inside \
                else "(no bench span)"
            out[name] += (b - a) * 1e-9
    return out


def op_seconds(trace: Trace, match, lo: float, hi: float,
               line: str = "ops") -> float:
    """Summed durations of matching events inside the window, averaged over
    the device planes."""
    tot = 0.0
    for d in trace.devices.values():
        tot += sum(min(e, hi) - max(s, lo) for n, s, e in d[line]
                   if match(n) and e > lo and s < hi)
    return tot / len(trace.devices) * 1e-9


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device operations that took most (self) time and the idle time
    by host span, each averaged over the device planes, in seconds."""
    ops, idle = collections.Counter(), collections.Counter()
    for d in trace.devices.values():
        for n, t, s, e in self_times(d["ops"]):
            if lo <= s and e <= hi:
                ops[short_name(n)] += t
        idle.update(attribute(gaps(d["ops"], lo, hi), trace.spans))
    k = len(trace.devices)
    return {"device_ops": [[n, v / k] for n, v in ops.most_common(top)],
            "idle_gaps": [[n, v / k] for n, v in idle.most_common(top)]}
