"""The telemetry recorder — the engines' single accounting surface.

Two layers, mirroring the ``REPRO_CONTRACTS`` arming pattern
(repro.analysis.contracts):

* **Accounting** (always on): counters, gauges, device-scalar
  accumulators, and the span ring.  These ARE the engines' runtime
  bookkeeping — ``events_processed``, ``agg_counter``, ``uplink_coords``,
  … live here and the old engine attributes are thin property views.
  Counter writes are plain dict arithmetic on host ints; ``accum`` adds
  device scalars eagerly WITHOUT syncing (the uplink-coords pattern: the
  value crosses to host exactly once, in :meth:`accum_value`, behind an
  ``expected_transfer``), so a disarmed recorder changes neither the
  engines' trajectories nor their host-transfer profile.
* **Emission** (armed only): the JSONL event stream (span, round,
  histogram and engine events) + run manifest sinks.  Armed via
  ``REPRO_OBS=on``, a session :func:`repro.obs.override`, or an explicit
  ``Recorder(armed=True)``.  Disarmed, every emission method is one
  boolean test and zero events are ever buffered or written.

**The span ring.**  Every span — :meth:`Recorder.span`, or the
module-level :func:`span` for code that holds no recorder — is kept,
armed or not, in one bounded in-process ring (the last ``RING_SIZE``
spans): its name, start and end in ``time.time_ns()``, the round it
belongs to and its enclosing span.  ``time.time_ns()`` is the host clock
the JAX profiler stamps its events on, and a trace's ``Task Environment``
plane records ``profile_start_time`` on it, so ``start_ns -
profile_start_time`` places a span on a trace's timeline exactly.  The
ring takes no lock and does no I/O, and a span never touches a device
value, so spans are legal inside ``CT.no_host_transfers``.  Any caller
in the process reads it with :func:`recent_spans` — after a slow or
stalled round, the phases of the last rounds and how long each took.

Every event carries the **dual clock**: ``sim`` is the caller-supplied
simulated time (the engines' SimClock / round clock — deterministic, so
fixed-seed event streams are engine-comparable) and ``wall`` is host
``time.time_ns()`` in seconds since recorder construction (real, so
spans price what instrumentation and training actually cost).  A span
with a ``sim`` time is part of the simulated round protocol and emits a
``span`` event; one without (an engine's own phases: stacking, write-back)
emits a ``phase`` event, which is not a sim kind.  Determinism tests
compare :meth:`sim_events` (wall fields stripped); profiling reads the
wall side.
"""
from __future__ import annotations

import collections
import contextlib
import json
import os
import subprocess
import threading
import time
import warnings
from typing import Dict, List, NamedTuple, Optional

import jax

from repro.analysis import contracts as CT

_TLS = threading.local()

#: event kinds whose payload is pure simulated-time/host arithmetic and
#: therefore engine-invariant for a fixed seed (the determinism wall in
#: tests/test_obs.py compares exactly these, wall clocks stripped)
SIM_KINDS = ("round", "span", "completion", "drop", "volumes")


def _env_enabled() -> bool:
    return os.environ.get("REPRO_OBS", "off").strip().lower() in (
        "on", "1", "true", "yes")


def enabled() -> bool:
    """Telemetry armed?  A session :func:`override` beats ``REPRO_OBS``."""
    ov = getattr(_TLS, "override", None)
    return _env_enabled() if ov is None else ov


@contextlib.contextmanager
def override(value: bool):
    """Force telemetry on/off for a scope (tests/benches flip in-process)."""
    prev = getattr(_TLS, "override", None)
    _TLS.override = bool(value)
    try:
        yield
    finally:
        _TLS.override = prev


def env_profile_round() -> Optional[int]:
    """Round index to capture a ``jax.profiler`` trace around
    (``REPRO_OBS_PROFILE=<round>``; unset/invalid = no trace)."""
    v = os.environ.get("REPRO_OBS_PROFILE", "").strip()
    try:
        return int(v)
    except ValueError:
        return None


def git_sha() -> str:
    """Current commit sha for the run manifest ("unknown" outside git)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


#: spans the ring keeps: some hundreds of rounds at ten spans per round
RING_SIZE = 4096

_RING: collections.deque = collections.deque(maxlen=RING_SIZE)


class Span(NamedTuple):
    """One finished span: ``start_ns``/``end_ns`` on ``time.time_ns()``;
    ``round`` is the round it belongs to (its own tag, else its enclosing
    span's), ``parent`` the enclosing span's name."""
    name: str
    start_ns: int
    end_ns: int
    round: Optional[int]
    parent: Optional[str]


def recent_spans() -> List[Span]:
    """The spans in the ring, in the order they ended (oldest first)."""
    return [Span(*s) for s in list(_RING)]


def _open_spans() -> list:
    try:
        return _TLS.spans
    except AttributeError:
        _TLS.spans = []
        return _TLS.spans


class _RingSpan:
    """Context manager behind :func:`span` and :meth:`Recorder.span`: on
    exit, one tuple into the ring, and the JSONL event if ``rec`` is an
    armed recorder."""
    __slots__ = ("name", "round", "parent", "t0", "rec", "sim", "tags")

    def __init__(self, name, rnd=None, rec=None, sim=None, tags=None):
        self.name, self.round, self.rec = name, rnd, rec
        self.sim, self.tags = sim, tags

    def __enter__(self):
        stack = _open_spans()
        self.parent = stack[-1] if stack else None
        if self.round is None and self.parent is not None:
            self.round = self.parent.round
        stack.append(self)
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.time_ns()
        _open_spans().pop()
        _RING.append((self.name, self.t0, t1, self.round,
                      None if self.parent is None else self.parent.name))
        if self.rec is not None:
            fields = dict(self.tags)
            if self.round is not None:
                fields.setdefault("round", self.round)
            self.rec.event("span" if self.sim is not None else "phase",
                           sim=self.sim, name=self.name,
                           wall_ms=(t1 - self.t0) * 1e-6, **fields)
        return False


def span(name: str, round: Optional[int] = None) -> _RingSpan:
    """A span into the ring only, for code that holds no recorder (its
    round is its enclosing span's unless given)."""
    return _RingSpan(name, round)


class Recorder:
    """Counters + gauges + device accumulators (always) and dual-clock
    spans + histograms + JSONL event log + run manifest (armed only).

    One recorder per engine run (constructed in ``FLRun.__post_init__``);
    pass ``recorder=`` to share one across runs or to arm explicitly.
    """

    def __init__(self, armed: Optional[bool] = None,
                 manifest: Optional[dict] = None,
                 profile_round: Optional[int] = None,
                 profile_dir: str = "obs_profile"):
        self.armed = enabled() if armed is None else bool(armed)
        self.manifest: dict = dict(manifest or {})
        self.counters: Dict[str, int] = {}
        self.gauges: Dict[str, float] = {}
        self.hists: Dict[str, List[float]] = {}
        self.events: List[dict] = []
        self._accums: Dict[str, jax.Array] = {}
        self._t0_ns = time.time_ns()
        self.profile_round = env_profile_round() \
            if profile_round is None else profile_round
        self.profile_dir = profile_dir

    # -- accounting surface (always on) ---------------------------------
    def inc(self, name: str, n: int = 1) -> int:
        self.counters[name] = self.counters.get(name, 0) + n
        return self.counters[name]

    def set(self, name: str, value: int) -> None:
        self.counters[name] = value

    def set_max(self, name: str, value) -> None:
        self.counters[name] = max(self.counters.get(name, value), value)

    def count(self, name: str, default: int = 0) -> int:
        return self.counters.get(name, default)

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = value

    def accum(self, name: str, value) -> None:
        """Accumulate a DEVICE scalar eagerly — no host sync; the running
        sum stays on device until :meth:`accum_value`."""
        prev = self._accums.get(name)
        self._accums[name] = value if prev is None else prev + value

    def accum_raw(self, name: str, default=None):
        """The device accumulator itself, unsynced (legacy attribute
        views hand this out so callers can keep adding device-side)."""
        return self._accums.get(name, default)

    def accum_value(self, name: str, default: float = 0.0) -> float:
        """The one intended sync point for a device accumulator."""
        v = self._accums.get(name)
        if v is None:
            return default
        with CT.expected_transfer("obs.accum_value[" + name + "]"):
            return float(v)                    # repro: noqa[R3]

    # -- emission (armed only) ------------------------------------------
    def event(self, kind: str, *, sim: Optional[float] = None,
              **fields) -> None:
        """Append one telemetry event (host values only — emission inside
        a ``no_host_transfers`` section must never force a sync)."""
        if not self.armed:
            return
        ev: dict = {"kind": kind,
                    "wall": (time.time_ns() - self._t0_ns) * 1e-9}
        if sim is not None:
            ev["sim"] = sim
        ev.update(fields)
        self.events.append(ev)

    def observe(self, name: str, value: float) -> None:
        """Histogram observation (summarized at flush)."""
        if not self.armed:
            return
        self.hists.setdefault(name, []).append(value)

    def span(self, name: str, sim: Optional[float] = None, **tags):
        """A span into the ring (always); armed, also one ``span`` event
        (with a ``sim`` time) or ``phase`` event (without) carrying the
        measured wall duration and the tags."""
        return _RingSpan(name, tags.get("round"),
                         self if self.armed else None, sim, tags)

    @contextlib.contextmanager
    def maybe_profile(self, round_idx: int):
        """Capture a ``jax.profiler`` trace of ONE chosen round into
        ``profile_dir`` (armed + ``profile_round`` match); otherwise free.
        A backend that cannot profile gets a warning, not an error."""
        if not self.armed or self.profile_round is None or \
                round_idx != self.profile_round:
            yield
            return
        try:
            jax.profiler.start_trace(self.profile_dir)
        except Exception as e:               # backend without profiling
            warnings.warn(f"repro.obs: round {round_idx} not profiled: {e}")
            started = False
        else:
            started = True
        try:
            yield
        finally:
            if started:
                jax.profiler.stop_trace()

    # -- views / sinks --------------------------------------------------
    def sim_events(self, kinds=SIM_KINDS) -> List[dict]:
        """Events of engine-invariant kinds with wall clocks stripped —
        what the fixed-seed determinism wall compares."""
        out = []
        for ev in self.events:
            if ev["kind"] not in kinds:
                continue
            out.append({k: v for k, v in ev.items()
                        if k not in ("wall", "wall_ms")})
        return out

    def hist_summary(self) -> Dict[str, dict]:
        out = {}
        for name, vals in self.hists.items():
            s = sorted(vals)
            n = len(s)
            out[name] = {"count": n, "min": s[0], "max": s[-1],
                         "mean": sum(s) / n,
                         "p50": s[n // 2],
                         "p90": s[min((9 * n) // 10, n - 1)],
                         "p99": s[min((99 * n) // 100, n - 1)]}
        return out

    def snapshot(self) -> dict:
        """Current accounting census: counters, gauges (device
        accumulators synced here), histogram summaries."""
        gauges = dict(self.gauges)
        for name in self._accums:
            gauges[name] = self.accum_value(name)
        return {"counters": dict(self.counters), "gauges": gauges,
                "hists": self.hist_summary()}

    def flush(self, out_dir: str) -> dict:
        """Write the run log: ``events.jsonl`` (manifest line, one line
        per event, summary line) + ``manifest.json``.  Returns paths."""
        os.makedirs(out_dir, exist_ok=True)
        events_path = os.path.join(out_dir, "events.jsonl")
        manifest_path = os.path.join(out_dir, "manifest.json")
        summary = self.snapshot()
        summary["events"] = len(self.events)
        with open(manifest_path, "w") as f:
            json.dump(self.manifest, f, indent=2, default=str)
        with open(events_path, "w") as f:
            f.write(json.dumps({"kind": "manifest", **self.manifest},
                               default=str) + "\n")
            for ev in self.events:
                f.write(json.dumps(ev, default=str) + "\n")
            f.write(json.dumps({"kind": "summary", **summary},
                               default=str) + "\n")
        return {"events": events_path, "manifest": manifest_path,
                "summary": summary}
