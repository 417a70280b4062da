"""The telemetry layer (repro.obs): arming, determinism, accounting.

Pins the walls the observability PR claims:

* disarmed is FREE — a disarmed recorder buffers zero events and a run
  with telemetry off reproduces the armed run's trajectory bit-for-bit
  (accounting is engine bookkeeping either way; emission never touches
  the math);
* the legacy engine counters (``events_processed``, ``agg_counter``,
  ``uplink_*``, ``snapshot_*``) are thin views over the recorder — the
  ONE accounting surface;
* fixed-seed sim-time event streams are ENGINE-INVARIANT: the sync trio
  (sequential / batched / sharded) emits identical ``sim_events()``, and
  the async pair (sequential reference / bucketed) emits identical
  completion+drop streams;
* every history row names its recording cadence (``round`` / ``event`` /
  ``bucket``) and the bucketed cadence records a SUBSET of the
  sequential event cadence's cycles (one row per bucket, never per
  event — the documented divergence, now pinned instead of silent);
* downlink accounting is the dense-broadcast twin of uplink (equal for
  uncompressed schemes, half of SCAFFOLD's 2x uplink);
* telemetry composes with the contract walls: REPRO_OBS=on under
  REPRO_CONTRACTS=on adds no host transfers and no compiled programs;
* the ``repro.obs report``/``diff`` CLI renders a flushed run log and
  exits nonzero on an injected regression, and the
  benchmarks/check_regression.py gates fire on the invariants they
  state.
"""
import os

if os.environ.get("REPRO_HOST_DEVICES") and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_HOST_DEVICES"])

import importlib.util
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts as CT
from repro.configs import CNNS, HeliosConfig, reduced
from repro.data.federated import partition_iid
from repro.data.synthetic import class_gaussian_images
from repro.federated import (AsyncFLRun, BatchedFLRun, FLRun, ShardedFLRun,
                             make_fleet, setup_clients)
from repro.obs import recorder as OBS
from repro.obs import report as OBR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUNDS = 2


@pytest.fixture(scope="module")
def setting():
    cfg = reduced(CNNS["lenet"])
    imgs, labels = class_gaussian_images(400, cfg.image_size,
                                         cfg.in_channels, cfg.num_classes,
                                         seed=0)
    ti, tl = class_gaussian_images(64, cfg.image_size, cfg.in_channels,
                                   cfg.num_classes, seed=9)
    parts = partition_iid(len(labels), 8, seed=0)
    return cfg, {"images": imgs, "labels": labels}, \
        {"images": ti, "labels": tl}, parts


def _make(setting, cls, scheme="helios", **kw):
    cfg, train, test, parts = setting
    hcfg = HeliosConfig()
    clients = setup_clients(make_fleet(4, 4), parts, hcfg)
    return cls(cfg, hcfg, scheme, clients, train, test,
               local_steps=1, batch_size=8, lr=0.1, seed=0, eval_batch=64,
               **kw)


def _diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# recorder unit behavior
# ---------------------------------------------------------------------------


def test_disarmed_recorder_counts_but_never_emits():
    rec = OBS.Recorder(armed=False)
    rec.inc("a")
    rec.inc("a", 2)
    rec.set_max("m", 5)
    rec.set_max("m", 3)
    rec.gauge("g", 1.5)
    rec.event("round", sim=0.0, x=1)
    rec.observe("h", 1.0)
    with rec.span("s", sim=0.0):
        pass
    assert rec.counters == {"a": 3, "m": 5}
    assert rec.gauges == {"g": 1.5}
    assert rec.events == [] and rec.hists == {}
    rec.accum("c", jnp.float32(2.0))
    rec.accum("c", jnp.float32(3.0))
    assert rec.accum_value("c") == 5.0
    assert rec.accum_value("missing", 7.0) == 7.0


def test_armed_recorder_flush_roundtrip(tmp_path):
    rec = OBS.Recorder(armed=True, manifest={"engine": "unit"})
    rec.event("round", sim=1.0, round=0)
    rec.observe("staleness", 2.0)
    with rec.span("train", sim=1.0, round=0):
        pass
    out = rec.flush(str(tmp_path / "run"))
    lines = [json.loads(line)
             for line in open(out["events"]) if line.strip()]
    assert lines[0]["kind"] == "manifest" and lines[0]["engine"] == "unit"
    assert lines[-1]["kind"] == "summary" and lines[-1]["events"] == 2
    assert json.load(open(out["manifest"]))["engine"] == "unit"
    # sim view strips the wall clock but keeps every sim-side field
    sims = rec.sim_events()
    assert [e["kind"] for e in sims] == ["round", "span"]
    assert all("wall" not in e and "wall_ms" not in e for e in sims)
    assert out["summary"]["hists"]["staleness"]["count"] == 1


@pytest.mark.parametrize("armed", [False, True], ids=["disarmed", "armed"])
def test_span_ring_keeps_every_span_bounded(armed):
    """Spans go to the ring armed or not: name, time_ns start and end, the
    round (inherited from the enclosing span) and the parent's name.  Only
    an armed recorder emits events: ``span`` with a sim time, ``phase``
    without (not a sim kind)."""
    rec = OBS.Recorder(armed=armed)
    t0 = time.time_ns()
    with rec.span("outer", sim=1.0, round=3):
        with rec.span("inner"):
            with OBS.span("leaf"):
                pass
    t1 = time.time_ns()
    leaf, inner, outer = OBS.recent_spans()[-3:]
    assert [s.name for s in (leaf, inner, outer)] == ["leaf", "inner",
                                                      "outer"]
    assert [(s.round, s.parent) for s in (leaf, inner, outer)] == [
        (3, "inner"), (3, "outer"), (3, None)]
    assert t0 <= outer.start_ns <= inner.start_ns <= leaf.start_ns \
        <= leaf.end_ns <= inner.end_ns <= outer.end_ns <= t1
    if armed:
        assert [(e["kind"], e["name"], e["round"]) for e in rec.events] \
            == [("phase", "inner", 3), ("span", "outer", 3)]
        assert [e["name"] for e in rec.sim_events()] == ["outer"]
    else:
        assert rec.events == []
    for i in range(OBS.RING_SIZE + 10):
        with OBS.span("fill", round=i):
            pass
    ring = OBS.recent_spans()
    assert len(ring) == OBS.RING_SIZE
    assert ring[-1].round == OBS.RING_SIZE + 9
    assert ring[0].round == 10


#: each sync round's phases, children of its ``fl.round`` span, in order
PHASES = ["fl.sample", "fl.stack", "fl.dispatch", "fl.writeback",
          "fl.adapt", "fl.evaluate"]


@pytest.mark.parametrize("cls", [BatchedFLRun, ShardedFLRun],
                         ids=["batched", "sharded"])
def test_sync_round_phases_in_the_ring(setting, cls):
    with OBS.override(False):
        run = _make(setting, cls)
        t0 = time.time_ns()
        run.run_sync(ROUNDS)
    spans = [s for s in OBS.recent_spans() if s.start_ns >= t0]
    for r in range(ROUNDS):
        (rnd,) = [s for s in spans if s.name == "fl.round" and s.round == r]
        kids = sorted((s for s in spans
                       if s.parent == "fl.round" and s.round == r),
                      key=lambda s: s.start_ns)
        assert [s.name for s in kids] == PHASES
        assert rnd.start_ns <= kids[0].start_ns
        assert kids[-1].end_ns <= rnd.end_ns
        for a, b in zip(kids, kids[1:]):         # siblings never overlap
            assert a.end_ns <= b.start_ns
    nested = [s for s in spans if s.parent == "fl.sample"]
    # both engines' samplers build the cohort's batches themselves (the
    # gather on the device, or the stack on the host): fl.stack nests there
    assert {(s.name, s.round) for s in nested} \
        == {("fl.stack", r) for r in range(ROUNDS)}


#: the round programs' named scopes (a trace's ``tf_op`` paths)
SCOPES = {"batched": ("_get_round_fn", ["fl_straggler_train",
                                        "fl_capable_train", "fl_aggregate"]),
          "sharded": ("_get_sharded_fn", ["fl_local_train", "fl_aggregate"])}


@pytest.mark.parametrize("cls", [BatchedFLRun, ShardedFLRun],
                         ids=["batched", "sharded"])
def test_round_program_carries_named_scopes(setting, cls):
    getter, scopes = SCOPES["sharded" if cls is ShardedFLRun else "batched"]
    with OBS.override(False):
        run = _make(setting, cls)
    texts = []
    get = getattr(run, getter)

    def spy(*args):
        fn = get(*args)

        def call(*xs):
            texts.append(fn.lower(*xs).as_text(debug_info=True))
            return fn(*xs)
        return call

    setattr(run, getter, spy)
    with OBS.override(False):
        run.run_sync(1)
    (text,) = texts
    for scope in scopes:
        assert f"/{scope}/" in text, scope


def test_masked_matmul_kernels_carry_their_names():
    from repro.kernels import ops

    def loss(x, w, m):
        h = ops.masked_dense(x, w, m, impl=ops.PALLAS, block_n=128)
        return jnp.sum(ops.masked_contract(h, w, m, impl=ops.PALLAS,
                                           block_n=128) ** 2)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        jnp.ones((8, 256)), jnp.ones((256, 256)), jnp.ones((256,)))

    def eqns(jx):
        for e in jx.eqns:
            yield e
            for p in e.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from eqns(inner)

    names = [str(e.params["name"]) for e in eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    # forward (dense fwd, contract fwd), then dense dx/dw and contract dh/dw
    assert sorted(names) == sorted(
        ["masked_matmul_fwd", "masked_matmul_dk", "masked_matmul_dk"]
        + ["masked_matmul_bwd"] * 3)


# ---------------------------------------------------------------------------
# disarmed is free; accounting views are back-compatible
# ---------------------------------------------------------------------------


def test_disarmed_run_zero_events_bit_identical_trajectory(setting):
    with OBS.override(False):
        off = _make(setting, BatchedFLRun)
        h_off = off.run_sync(ROUNDS)
    with OBS.override(True):
        on = _make(setting, BatchedFLRun)
        h_on = on.run_sync(ROUNDS)
    assert not off.rec.armed and off.rec.events == []
    assert on.rec.armed and on.rec.events
    assert _diff(off.global_params, on.global_params) == 0.0
    assert [h["acc"] for h in h_off] == [h["acc"] for h in h_on]
    # accounting is identical either way — it IS the engine bookkeeping
    assert off.rec.counters == {k: v for k, v in on.rec.counters.items()
                                if not k.startswith("contracts.")}


def test_legacy_counter_views_are_recorder_views(setting):
    with OBS.override(False):
        run = _make(setting, FLRun)
        run.run_sync(ROUNDS)
    n = ROUNDS * len(run.clients)
    assert run.uplink_updates == n == run.rec.count("uplink_updates")
    assert run.downlink_updates == n
    assert run.uplink_extra_updates == 0
    assert run.uplink_bytes() == run.downlink_bytes() > 0
    with OBS.override(False):
        arun = _make(setting, AsyncFLRun, "afo")
        arun.run_async(6)
    assert arun.events_processed == arun.rec.count("events_processed") > 0
    assert arun.agg_counter == arun.events_processed
    assert arun.snapshot_peak == arun.rec.count("snapshot_peak", 1) >= 1
    assert arun.snapshot_anchor_misses == 0
    assert arun.downlink_updates == arun.events_processed


def test_scaffold_uplink_is_twice_downlink(setting):
    with OBS.override(False):
        run = _make(setting, FLRun, "scaffold")
        run.run_sync(ROUNDS)
    assert run.uplink_extra_updates == run.uplink_updates
    assert run.uplink_bytes() == 2 * run.downlink_bytes()


# ---------------------------------------------------------------------------
# fixed-seed sim streams are engine-invariant
# ---------------------------------------------------------------------------


def test_sync_trio_identical_sim_event_streams(setting):
    streams = []
    for cls in (FLRun, BatchedFLRun, ShardedFLRun):
        with OBS.override(True):
            run = _make(setting, cls)
            run.run_sync(ROUNDS)
        streams.append(run.rec.sim_events())
    assert streams[0] == streams[1] == streams[2]
    kinds = {e["kind"] for e in streams[0]}
    assert {"round", "span", "volumes"} <= kinds


def test_async_pair_identical_completion_streams(setting):
    runs = []
    for cls in (FLRun, AsyncFLRun):
        with OBS.override(True):
            run = _make(setting, cls, "afo")
            run.run_async(6)
        runs.append(run)
    seq, buck = runs
    kinds = ("completion", "drop")
    assert seq.rec.sim_events(kinds) == buck.rec.sim_events(kinds)
    assert seq.rec.sim_events(("completion",))
    assert seq.agg_counter == buck.agg_counter
    assert seq.events_processed == buck.events_processed
    # the event core's own census: same arrival stream, same high water
    assert seq.rec.count("queue_peak") \
        == buck.rec.count("queue_peak") > 0


# ---------------------------------------------------------------------------
# record_cadence: every history row names how it was recorded
# ---------------------------------------------------------------------------


def test_record_cadence_pins_the_async_divergence(setting):
    with OBS.override(False):
        sync = _make(setting, FLRun)
        h_sync = sync.run_sync(ROUNDS)
        seq = _make(setting, FLRun, "afo")
        h_seq = seq.run_async(6)
        buck = _make(setting, AsyncFLRun, "afo")
        h_buck = buck.run_async(6)
    assert [h["record_cadence"] for h in h_sync] == ["round"] * len(h_sync)
    assert {h["record_cadence"] for h in h_seq} == {"event"}
    assert {h["record_cadence"] for h in h_buck} == {"bucket"}
    # the documented relationship at eval_every=1: the sequential
    # reference records at EVERY capable completion (cycles 1..N), the
    # bucketed engine once per bucket — its cycles are a subset of the
    # sequential ones and both end at the same completion count
    seq_cycles = [h["cycle"] for h in h_seq]
    buck_cycles = [h["cycle"] for h in h_buck]
    assert seq_cycles == list(range(1, len(seq_cycles) + 1))
    assert set(buck_cycles) <= set(seq_cycles)
    assert buck_cycles == sorted(buck_cycles)
    assert buck_cycles[-1] == seq_cycles[-1]
    # downlink grows monotonically in every cadence's rows
    for hist in (h_sync, h_seq, h_buck):
        mb = [h["downlink_mb"] for h in hist]
        assert mb == sorted(mb) and mb[-1] > 0


# ---------------------------------------------------------------------------
# telemetry under the contract walls
# ---------------------------------------------------------------------------


def test_obs_on_composes_with_contracts(setting):
    """REPRO_OBS=on under REPRO_CONTRACTS=on: the transfer guard and the
    compile budget run inside run_sync and must hold unchanged; the run
    log gains the contracts bridge (compile census + contract counters)
    and the compression error-store census."""
    CT.reset_counters()
    with OBS.override(True), CT.override(True):
        run = _make(setting, BatchedFLRun, compression="topk")
        run.run_sync(ROUNDS)
    assert run.rec.count("contracts.guarded_sections") \
        == CT.counters["guarded_sections"] > 0
    compile_evs = [e for e in run.rec.events if e["kind"] == "compile"]
    assert {e["seam"] for e in compile_evs} >= {"local_train"}
    store = [e for e in run.rec.events if e["kind"] == "error_store"]
    assert store and store[-1]["rows"] == len(run.clients)


# ---------------------------------------------------------------------------
# CLI: report renders, diff gates
# ---------------------------------------------------------------------------


def _cli(args, cwd=ROOT):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    return subprocess.run([sys.executable, "-m"] + args, cwd=cwd, env=env,
                          capture_output=True, text=True)


@pytest.mark.slow
def test_report_and_diff_cli(tmp_path, setting):
    with OBS.override(True):
        run = _make(setting, BatchedFLRun)
        run.run_sync(ROUNDS)
    out = run.rec.flush(str(tmp_path / "run"))
    r = _cli(["repro.obs", "report", str(tmp_path / "run")])
    assert r.returncode == 0, r.stderr
    for section in ("run manifest", "per-round table", "span census"):
        assert section in r.stdout
    # identical runs: no regression
    r = _cli(["repro.obs", "diff", out["events"], out["events"]])
    assert r.returncode == 0 and "no regressions" in r.stdout
    # injected regression fixture: halve the recorded accuracy
    bad = tmp_path / "bad.jsonl"
    with open(out["events"]) as f, open(bad, "w") as g:
        for line in f:
            ev = json.loads(line)
            if ev.get("kind") == "history" and "acc" in ev:
                ev["acc"] *= 0.5
            g.write(json.dumps(ev) + "\n")
    r = _cli(["repro.obs", "diff", out["events"], str(bad)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout


def test_summarize_and_diff_units(setting):
    with OBS.override(True):
        run = _make(setting, BatchedFLRun)
        hist = run.run_sync(ROUNDS)
        run._obs_finish("unit")
    summ = OBR.summarize(run.rec.events
                         + [{"kind": "summary", **run.rec.snapshot()}])
    assert summ["rounds"] == len(hist)
    assert summ["metric_name"] == "acc"
    assert summ["final_metric"] == hist[-1]["acc"]
    assert summ["uplink_mb"] == pytest.approx(run.uplink_bytes() / 1e6)
    assert summ["downlink_mb"] == pytest.approx(run.downlink_bytes() / 1e6)
    # loss-like metrics invert the better-direction: a LOWER ce is ok
    old = [{"kind": "history", "sim": 1.0, "cycle": 1, "ce": 2.0}]
    new = [{"kind": "history", "sim": 1.0, "cycle": 1, "ce": 1.0}]
    _, regressions = OBR.diff(old, new)
    assert not regressions
    _, regressions = OBR.diff(new, old)
    assert regressions == ["final_metric"]


# ---------------------------------------------------------------------------
# the CI regression gate fires on what it states
# ---------------------------------------------------------------------------


def _load_check_regression():
    spec = importlib.util.spec_from_file_location(
        "check_regression",
        os.path.join(ROOT, "benchmarks", "check_regression.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_regression_units():
    mod = _load_check_regression()
    obs = {"rounds": 3, "overhead_frac": 0.02,
           "results": {"off": {"events": 0}},
           "summary": {"counters": {"uplink_updates": 12,
                                    "downlink_updates": 12},
                       "sim_time": 3.0, "uplink_mb": 1.0,
                       "downlink_mb": 1.0, "metric_name": "acc",
                       "final_metric": 0.5}}
    problems = []
    mod.check_observability(obs, obs, problems, 1.0, 0.5)
    assert problems == []
    bad = json.loads(json.dumps(obs))
    bad["results"]["off"]["events"] = 7
    bad["summary"]["counters"]["downlink_updates"] = 0
    bad["overhead_frac"] = 0.9
    problems = []
    mod.check_observability(bad, obs, problems, 1.0, 0.5)
    assert len(problems) == 3

    gau = {"schemes": {
        "syn": {"engine": "BatchedFLRun", "uplink_mb": 1.0,
                "downlink_mb": 1.0},
        "scaffold": {"engine": "BatchedFLRun", "uplink_mb": 2.0,
                     "downlink_mb": 1.0}}}
    problems = []
    mod.check_gauntlet(gau, gau, problems)
    assert problems == []
    bad = json.loads(json.dumps(gau))
    bad["schemes"]["scaffold"]["uplink_mb"] = 1.0     # 2x cost vanished
    bad["schemes"]["syn"]["downlink_mb"] = 0.0
    problems = []
    mod.check_gauntlet(bad, gau, problems)
    assert len(problems) == 2

    con = {"results": {"off": {"counters": {"blocked_transfers": 0}},
                       "on": {"counters": {"finite_checks": 4}}}}
    problems = []
    mod.check_contracts(con, con, problems)
    assert problems == []
    bad = json.loads(json.dumps(con))
    bad["results"]["on"]["counters"]["finite_checks"] = 0
    problems = []
    mod.check_contracts(bad, con, problems)
    assert problems == ["on-mode check family finite_checks collapsed to "
                        "zero (committed ran 4)"]
