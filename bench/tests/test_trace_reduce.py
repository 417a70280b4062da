"""The trace reduction on a small trace recorded on one TPU v5 lite chip
(``data/small.xplane.pb``: a jitted 2048^3 matmul chain run three times
inside ``bench.compute`` spans, each followed by a 10 ms ``bench.sleep``,
all inside ``bench.window``; the source locations in its metadata are
renamed to ``bench/small_trace_src.py``), and on hand-made events.  On
this trace the device's clock reads 0.6-0.8 ms earlier than the host's,
so the first compute's operations fall just before the window opens and
are not counted."""
import os

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce as T

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return T.from_profile(ProfileData.from_file(SMALL))


def test_planes_and_spans(small):
    assert list(small.devices) == ["/device:TPU:0"]
    ops = small.devices["/device:TPU:0"]["ops"]
    assert len(ops) == 12
    assert [m[0].split("(")[0] for m in
            small.devices["/device:TPU:0"]["modules"]] == ["jit__lambda"] * 3
    names = [s[0] for s in small.spans]
    assert names.count("bench.compute") == 3
    assert names.count("bench.sleep") == 3
    assert small.window() == (45057571.0, 80552768.0)


def test_busy_union_and_idle_share(small):
    lo, hi = small.window()
    # the 12 operations do not overlap: the union is their summed duration
    ops = small.devices["/device:TPU:0"]["ops"]
    assert T.busy_ns(ops, 0, 1e12) == sum(e - s for _, s, e in ops) \
        == 543540.0
    busy = T.mean_busy(small, lo, hi)
    assert busy == pytest.approx(362347e-9)
    window = (hi - lo) * 1e-9
    assert window == pytest.approx(0.035495197)
    assert 1 - busy / window == pytest.approx(0.989792, abs=1e-6)


def test_per_name_kernel_time(small):
    lo, hi = small.window()
    conv = T.op_seconds(small, lambda n: n.startswith(
        "%convolution_tanh_fusion"), lo, hi)
    assert conv == pytest.approx((89713 + 89713) * 1e-9)
    mods = T.op_seconds(small, lambda n: n.startswith("jit__lambda"), lo, hi,
                        line="modules")
    assert mods == pytest.approx((181217 + 181151) * 1e-9)
    top = T.breakdown(small, lo, hi)["device_ops"]
    assert [n for n, _ in top[:2]] == ["%fusion", "%convolution_tanh_fusion"]


def test_span_attribution(small):
    lo, hi = small.window()
    idle = dict(T.breakdown(small, lo, hi)["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        (hi - lo) * 1e-9 - T.mean_busy(small, lo, hi))
    # the three 10 ms sleeps hold most of the idle time (each compute's
    # operations land just before its host span, by the clocks' skew)
    assert idle["bench.sleep"] == pytest.approx(0.031703102)
    assert idle["bench.sleep"] > 0.85 * sum(idle.values())


def test_union_gaps_and_nesting_by_hand():
    ev = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 40, 50)]
    assert T.union(ev, 0, 60) == [[0, 15], [20, 30], [40, 50]]
    assert T.gaps(ev, 0, 60) == [(15, 20), (30, 40), (50, 60)]
    assert T.busy_ns(ev, 8, 45) == 7 + 10 + 5
    spans = [("bench.window", 0, 60), ("bench.sample", 12, 35)]
    got = T.attribute(T.gaps(ev, 0, 60), spans)
    assert got == {"bench.sample": pytest.approx(10e-9),
                   "bench.window": pytest.approx(15e-9)}
    # a loop event enclosing two body events keeps only its own time
    loop = [("%while.1 = ...", 0, 100), ("%f.1 = ...", 10, 30),
            ("%f.2 = ...", 40, 90)]
    own = {n: t for n, t, _, _ in T.self_times(loop)}
    assert own == {"%while.1 = ...": pytest.approx(30e-9),
                   "%f.1 = ...": pytest.approx(20e-9),
                   "%f.2 = ...": pytest.approx(50e-9)}
    assert T.short_name('%cc.3 = f32[8] custom-call(x), '
                        'custom_call_target="tpu_custom_call"') \
        == "%cc.3 tpu_custom_call"


def test_host_spans_placed_on_the_trace_clock():
    """``run.HostSpans.place``: the window closes where the device's last
    operation ends, and host-clock spans keep their offsets from there."""
    from bench.run import HostSpans
    t = T.from_profile(ProfileData.from_file(SMALL))
    hs = HostSpans.__new__(HostSpans)
    hs.spans = [("bench.sample", 10.0, 10.002), ("bench.sample", 10.5, 10.6)]
    hs.place(t, [9.9, 10.3, 10.7])
    end = 68086769.0
    assert t.last_device_end() == end
    assert t.window() == (pytest.approx(end - 0.8e9), end)
    assert t.spans[1] == ("bench.sample", pytest.approx(end - 0.7e9),
                          pytest.approx(end - 0.698e9))
