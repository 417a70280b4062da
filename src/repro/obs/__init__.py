"""Observability: the engines' unified telemetry layer.

Zero-overhead-when-off (mirrors the ``REPRO_CONTRACTS`` arming pattern):
arm with ``REPRO_OBS=on`` or a session :func:`override`.  The
:class:`Recorder` is the single accounting surface — engine counters
(``events_processed``, ``agg_counter``, ``uplink_coords``, …) live here
and the old engine attributes are thin views.  Spans are always kept in a
bounded in-process ring on the profiler's host clock
(:func:`recent_spans`).  Armed, the recorder additionally buffers
dual-clock (sim + wall) events, spans, and histograms, flushed to a JSONL
event log + run manifest that ``python -m repro.obs report|diff`` renders
and regression-gates.
"""
from repro.obs.recorder import (  # noqa: F401
    RING_SIZE,
    Recorder,
    SIM_KINDS,
    Span,
    enabled,
    env_profile_round,
    git_sha,
    override,
    recent_spans,
    span,
)
from repro.obs.report import (  # noqa: F401
    diff,
    load_events,
    render,
    summarize,
)
