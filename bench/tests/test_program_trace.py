"""The program's spans and named scopes on a trace's clock
(``bench/program_trace.py``) and the per-layer readers built on them.

``data/small.xplane.pb`` is the trace ``test_trace_reduce.py`` describes.
``data/scoped.xplane.pb`` was recorded on one TPU v5 lite chip by
``record_scoped_trace.py``: a jitted function with two named scopes
(``probe_dense``, ``probe_kernel``, the latter the masked-matmul kernel
named ``masked_matmul_fwd``) run three times, each call inside a
``repro.obs`` span and a ``TraceAnnotation`` of the same name;
``data/scoped.spans.json`` holds the spans the ring stamped in that run."""
import importlib.util
import json
import os

import pytest
from jax.profiler import ProfileData

from bench import program_trace as PT
from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
SCOPED = os.path.join(DATA, "scoped.xplane.pb")
METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_small_trace_start_time_and_tf_op():
    xs = PT.read_xspace(SMALL)
    assert PT.profile_start_ns(xs) == 1792302422599916869
    ops = PT.device_ops(xs)
    assert list(ops) == ["/device:TPU:0"]
    names = {n for n, _, _ in ops["/device:TPU:0"]}
    assert "jit(<lambda>)/dot_general:" in names
    # the same events at the same times as ProfileData gives them
    ref = T.from_profile(ProfileData.from_file(SMALL))
    got = ops["/device:TPU:0"]
    want = ref.devices["/device:TPU:0"]["ops"]
    assert len(got) == len(want) == 12
    assert [(s, e) for _, s, e in got] == [(s, e) for _, s, e in want]


def _ctx(spans=(), ops=None, lo=0.0, hi=1000.0, rounds=2):
    pt = PT.ProgramTrace({d: T.self_times(o)
                          for d, o in (ops or {}).items()}, list(spans))
    return {"program_trace": pt, "lo": lo, "hi": hi, "rounds": rounds}


#: two rounds of host spans, in ns: fl.sample encloses a nested fl.stack
#: (the sharded sampler's), the second round's fl.evaluate runs past the
#: window's end at 1000
SPANS = [("fl.round", 0, 400), ("fl.sample", 10, 110), ("fl.stack", 60, 90),
         ("fl.stack", 110, 150), ("fl.dispatch", 150, 160),
         ("fl.writeback", 160, 300), ("fl.evaluate", 300, 400),
         ("fl.round", 500, 1100), ("fl.stack", 510, 550),
         ("fl.writeback", 560, 700), ("fl.evaluate", 900, 1100)]


@pytest.mark.parametrize("metric,ms", [
    ("stack_ms", (30 + 40 + 40) * 1e-6 / 2),
    ("writeback_ms", (140 + 140) * 1e-6 / 2),
    # 100 of the second evaluation's 200 ns fall inside the window
    ("eval_ms", (100 + 100) * 1e-6 / 2),
])
def test_span_readers_self_time_clipped_per_round(metric, ms):
    assert _reader(metric)(_ctx(SPANS)) == pytest.approx(ms)
    assert _reader(metric)(_ctx([("fl.round", 0, 400)])) is None


#: two device planes; a loop op under the straggler scope encloses the
#: kernel; the aggregate's second op starts before the window opens at 100
OPS = {
    "/device:TPU:0": [
        ("jit(round_fn)/fl_straggler_train/while:while", 100, 300),
        ("jit(round_fn)/fl_straggler_train/vmap(jvp(jit(masked_matmul)))/"
         "masked_matmul_fwd/pallas_call:custom-call", 150, 250),
        ("jit(round_fn)/fl_capable_train/vmap(jvp(jit(masked_matmul)))/"
         "masked_matmul_fwd/pallas_call:custom-call", 300, 500),
        ("jit(round_fn)/fl_aggregate/dot_general:fusion", 500, 520),
        ("jit(round_fn)/copy:copy", 520, 530)],
    "/device:TPU:1": [
        ("jit(round_body)/shard_map/fl_aggregate/psum:all-reduce", 50, 150),
        ("jit(round_body)/shard_map/fl_local_train/add:fusion", 150, 350)],
}


@pytest.mark.parametrize("metric,ms", [
    # self time, not the loop's whole span: 200 - 100 + the kernel's 100
    ("straggler_train_ms", 200 * 1e-6 / 2 / 2),
    ("capable_train_ms", 200 * 1e-6 / 2 / 2),
    # 20 on chip 0; half of the 100 ns psum lies inside the window
    ("aggregate_ms", (20 + 50) * 1e-6 / 2 / 2),
])
def test_scope_readers_self_time_clipped_per_round(metric, ms):
    assert _reader(metric)(_ctx(ops=OPS, lo=100, hi=1000)) \
        == pytest.approx(ms)
    assert _reader(metric)(_ctx(ops={"/device:TPU:0": OPS[
        "/device:TPU:0"][-1:]})) is None
    assert _reader(metric)({"program_trace": None}) is None


def test_scope_matches_a_path_component():
    assert PT.in_scope("jit(f)/fl_aggregate/psum:all-reduce", "fl_aggregate")
    assert not PT.in_scope("jit(f)/fl_aggregate_x/add:add", "fl_aggregate")
    assert not PT.in_scope("jit(f)/vmap(transpose(fl_aggregate))/add:add",
                           "fl_aggregate")


def test_idle_by_program_span():
    trace = T.Trace({"/device:TPU:0": {"ops": [("a", 0, 100),
                                                ("b", 300, 400)],
                                       "modules": []}}, [])
    ctx = _ctx([("fl.round", 0, 400), ("fl.sample", 100, 250)], rounds=1,
               hi=400)
    ctx["trace"] = trace
    from bench import phases
    idle = phases.idle_by_span(ctx)
    assert idle == {"fl.sample": pytest.approx(150e-9),
                    "fl.round": pytest.approx(50e-9)}


def test_ring_spans_placed_by_the_trace_start():
    from repro import obs
    with obs.span("probe.outer", round=7):
        with obs.span("probe.inner"):
            pass
    inner, outer = [s for s in obs.recent_spans()
                    if s.name.startswith("probe.")][-2:]
    start = outer.start_ns - 1000
    placed = [p for p in PT.placed_spans(start)
              if p[0].startswith("probe.")][-2:]
    assert placed == [("probe.inner", float(inner.start_ns - start),
                       float(inner.end_ns - start)),
                      ("probe.outer", 1000.0, float(outer.end_ns - start))]
    assert (inner.round, inner.parent) == (7, "probe.outer")
    assert PT.placed_spans(None) == []


@pytest.fixture(scope="module")
def scoped():
    xs = PT.read_xspace(SCOPED)
    with open(os.path.join(DATA, "scoped.spans.json")) as f:
        spans = json.load(f)
    return xs, spans


def test_scoped_trace_names_scopes_and_kernel(scoped):
    xs, _ = scoped
    (ops,) = PT.device_ops(xs).values()
    dense = [n for n, _, _ in ops if PT.in_scope(n, "probe_dense")]
    kernel = [n for n, _, _ in ops if PT.in_scope(n, "probe_kernel")]
    assert dense and kernel
    assert any("masked_matmul_fwd" in n for n in kernel)
    ref = T.from_profile(ProfileData.from_file(SCOPED))
    (dev,) = ref.devices.values()
    assert any("masked_matmul" in T.short_name(n) for n, _, _ in dev["ops"])


def test_ring_spans_land_on_their_annotations(scoped):
    """The ring's stamps, placed by ``profile_start_time``, agree with the
    profiler's own copies of the same spans within 0.1 ms."""
    xs, spans = scoped
    start = PT.profile_start_ns(xs)
    ann = sorted((e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for plane in ProfileData.from_file(SCOPED).planes
                 for line in plane.lines for e in line.events
                 if e.name.startswith("probe."))
    mine = sorted((s["name"], s["start_ns"] - start, s["end_ns"] - start)
                  for s in spans)
    assert len(ann) == len(mine) == 6
    for (n, a, b), (m, s, e) in zip(ann, mine):
        assert n == m
        assert abs(a - s) < 1e5 and abs(b - e) < 1e5


def test_phases_split_the_round_program_by_scope():
    from bench import phases
    ops = OPS["/device:TPU:0"]
    ctx = _ctx(SPANS, {"/device:TPU:0": ops}, lo=0, hi=1000)
    hlo = [(f"%op.{i} = f32[]", s, e) for i, (_, s, e) in enumerate(ops)]
    hlo[1] = ("%closed_call.3 = f32[] call()", 150, 250)
    hlo[2] = ("%masked_matmul_fwd.1 = f32[] custom-call(), "
              'custom_call_target="tpu_custom_call"', 300, 500)
    ctx["trace"] = T.Trace({"/device:TPU:0": {
        "ops": hlo, "modules": [("jit_round_fn(1)", 100, 520),
                                ("jit_eval(2)", 520, 540)]}}, [])
    got = phases.device_scopes(ctx)
    assert got["scopes_ms"] == {
        "fl_straggler_train": pytest.approx(200e-6 / 2),
        "fl_capable_train": pytest.approx(200e-6 / 2),
        "fl_aggregate": pytest.approx(20e-6 / 2)}
    kern = got["kernel_events"]
    assert sorted(kern) == ["%closed_call.3",
                            "%masked_matmul_fwd.1 tpu_custom_call"]
    assert "masked_matmul_fwd" in kern["%closed_call.3"]["tf_op"]
    ctx["hi"] = 1200                 # the last 100 ns lie under no span
    host = phases.host_phases(ctx)
    assert host["host_ms"]["fl.stack"] == pytest.approx(110e-6 / 2)
    assert host["idle_under_spans"] == pytest.approx((100 + 570) / 770)
