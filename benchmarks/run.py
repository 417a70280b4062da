"""Benchmark harness — one function per paper table/figure.

Output convention: ``name,us_per_call,derived`` CSV rows.
  * FL tables: name = table/scheme/setting, us_per_call = simulated wall
    time per aggregation cycle (in microtime units x1e6), derived = accuracy
    or speedup.
  * kernel benches: us_per_call = wall microseconds per call (CPU interpret
    for Pallas), derived = allclose max-error vs the oracle.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only t1,t2]
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import xla_env
from repro.analysis import contracts as CT
from repro.configs import CNNS, HeliosConfig, reduced
from repro.core import theory
from repro.data.federated import (partition_iid, partition_noniid,
                                  partition_noniid_lazy)
from repro.data.synthetic import class_gaussian_images
from repro.federated import (SCHEMES, AsyncFLRun, BatchedFLRun, FLRun,
                             make_fleet, make_scheme, setup_clients)

ROWS = []


def emit(name: str, us_per_call: float, derived):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


#: task difficulty calibrated so convergence takes 10+ rounds (the paper's
#: CIFAR regime) — full LeNet; reduced AlexNet/ResNet for CPU cost.
_NOISE = {"lenet": 6.0, "alexnet": 3.0, "resnet18": 3.0}


def _world(model: str, n_clients: int, noniid: bool = True, seed: int = 0):
    cfg = CNNS[model] if model == "lenet" else reduced(CNNS[model])
    noise = _NOISE.get(model, 4.0)
    imgs, labels = class_gaussian_images(
        2000, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=seed,
        noise=noise)
    ti, tl = class_gaussian_images(
        512, cfg.image_size, cfg.in_channels, cfg.num_classes,
        seed=seed + 99, noise=noise)
    if noniid:
        parts = partition_noniid(labels, n_clients, shards_per_client=4,
                                 seed=seed)
    else:
        parts = partition_iid(len(labels), n_clients, seed=seed)
    return cfg, imgs, labels, ti, tl, parts


def _run_scheme(world, scheme, n_capable, n_straggler, rounds, lr=0.02,
                hcfg=None, seed=0):
    cfg, imgs, labels, ti, tl, parts = world
    hcfg = hcfg or HeliosConfig()
    clients = setup_clients(make_fleet(n_capable, n_straggler), parts, hcfg)
    run = FLRun(cfg, hcfg, scheme, clients,
                {"images": imgs, "labels": labels},
                {"images": ti, "labels": tl},
                local_steps=2, lr=lr, seed=seed)
    # the Scheme object is the one authority on sync-vs-event execution
    # (the old inline name list here silently ran new sync schemes async)
    if make_scheme(scheme).async_native:
        hist = run.run_async(rounds)
    else:
        hist = run.run_sync(rounds)
    return hist


def _run_worker(module: str, args, env: dict, timeout: float):
    """Run ``python -m module args`` as a worker with its own JAX.

    A chip belongs to one process: a parent that has already started an
    accelerator backend holds it, and a worker that needs it would then
    fail or hang.  So that case is refused up front; workers pinned to the
    CPU (``JAX_PLATFORMS=cpu``) never touch the chip and always run.
    """
    import subprocess
    import sys
    from jax._src import xla_bridge

    if env.get("JAX_PLATFORMS") != "cpu" and \
            xla_bridge.backends_are_initialized() and \
            jax.default_backend() != "cpu":
        raise RuntimeError(
            f"benchmarks.run: this process already holds the "
            f"{jax.default_backend()} device, so worker {module} could not "
            f"use it; run this table alone (--only) in a fresh process")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(env, PYTHONPATH=os.path.join(repo, "src"))
    r = subprocess.run([sys.executable, "-m", module, *args], env=env,
                       cwd=repo, capture_output=True, text=True,
                       timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"worker {module} exited {r.returncode}:\n"
                           + r.stdout[-2000:] + r.stderr[-2000:])
    return r.stdout


def _acc_at_time(hist, t):
    best = 0.0
    for h in hist:
        if h["time"] <= t:
            best = max(best, h["acc"])
    return best


def _time_to_acc(hist, target):
    for h in hist:
        if h["acc"] >= target:
            return h["time"]
    return float("inf")


# ---------------------------------------------------------------------------
# Fig. 5 / §VII.B: convergence accuracy, 4- and 6-device settings
# ---------------------------------------------------------------------------


def table_convergence(models=("lenet", "alexnet", "resnet18"), rounds=14):
    for model in models:
        for (nc, ns) in ((2, 2), (3, 3)):
            world = _world(model, nc + ns)
            for scheme in ("syn", "asyn", "random", "afo", "helios"):
                hist = _run_scheme(world, scheme, nc, ns, rounds)
                cyc_t = hist[-1]["time"] / max(hist[-1]["cycle"], 1)
                emit(f"fig5/{model}/{nc + ns}dev/{scheme}", cyc_t * 1e6,
                     f"acc={hist[-1]['acc']:.3f}")


# ---------------------------------------------------------------------------
# §VII.B: speedup vs Syn FL (paper: up to 2.5x)
# ---------------------------------------------------------------------------


def table_speedup(model="lenet", rounds=16, target=0.4):
    for (nc, ns) in ((2, 2), (3, 3)):
        world = _world(model, nc + ns)
        base = _run_scheme(world, "syn", nc, ns, rounds)
        t_syn = _time_to_acc(base, target)
        for scheme in ("helios", "random", "afo"):
            hist = _run_scheme(world, scheme, nc, ns, rounds * 3
                               if scheme == "helios" else rounds)
            t = _time_to_acc(hist, target)
            sp = t_syn / t if np.isfinite(t) else 0.0
            emit(f"speedup/{model}/{nc + ns}dev/{scheme}",
                 (t if np.isfinite(t) else -1) * 1e6,
                 f"speedup_vs_syn={sp:.2f}x")


# ---------------------------------------------------------------------------
# Fig. 6 / §VII.C: aggregation optimization (Helios vs S.T. Only)
# ---------------------------------------------------------------------------


def table_aggregation_opt(model="lenet", rounds=10):
    for ns in (1, 2, 3, 4):
        world = _world(model, 2 + ns)
        h_st = _run_scheme(world, "st_only", 2, ns, rounds)
        h_he = _run_scheme(world, "helios", 2, ns, rounds)
        gain = h_he[-1]["acc"] - h_st[-1]["acc"]
        emit(f"fig6/{model}/{ns}stragglers/helios_vs_st_only",
             h_he[-1]["time"] / rounds * 1e6,
             f"acc_st={h_st[-1]['acc']:.3f};acc_helios={h_he[-1]['acc']:.3f};"
             f"gain={gain:+.3f}")


# ---------------------------------------------------------------------------
# Fig. 7 / §VII.D: Non-IID evaluation
# ---------------------------------------------------------------------------


def table_noniid(model="lenet", rounds=12):
    for (nc, ns) in ((2, 2), (3, 3)):
        for noniid in (False, True):
            world = _world(model, nc + ns, noniid=noniid)
            for scheme in ("syn", "asyn", "helios"):
                hist = _run_scheme(world, scheme, nc, ns, rounds)
                tag = "noniid" if noniid else "iid"
                emit(f"fig7/{model}/{nc + ns}dev/{tag}/{scheme}",
                     hist[-1]["time"] / max(hist[-1]["cycle"], 1) * 1e6,
                     f"acc={hist[-1]['acc']:.3f}")


# ---------------------------------------------------------------------------
# ablation: P_s (top-contribution fraction, Section VI.A: "0.05 to 0.1")
# ---------------------------------------------------------------------------


def table_ps_ablation(model="lenet", rounds=10):
    """P_s=0 is pure-random rotation (≈ Caldas); large P_s freezes the
    rotation (top units monopolize).  The paper picks 0.05-0.1."""
    world = _world(model, 4)
    for p_s in (0.0, 0.05, 0.1, 0.3):
        hcfg = HeliosConfig(p_s=p_s)
        hist = _run_scheme(world, "helios", 2, 2, rounds, hcfg=hcfg)
        emit(f"ablation/p_s={p_s}", hist[-1]["time"] / rounds * 1e6,
             f"acc={hist[-1]['acc']:.3f}")


# ---------------------------------------------------------------------------
# scheme gauntlet: every registered scheme under ONE heterogeneous world
# ---------------------------------------------------------------------------


def _prop2_report(straggler):
    """Prop. 2 numbers for one straggler's CURRENT contribution scores:
    the Wangni sampling distribution at its adapted volume, the Eq. 6
    variance inflation that distribution pays, and the Eq. 9 expected-
    sparsity bound — the theory column of the gauntlet (what soft
    training costs in gradient variance at the volume it settled on)."""
    g = jnp.concatenate(
        [jnp.asarray(v, jnp.float32).ravel()
         for v in jax.tree.leaves(straggler.helios_state["scores"])])
    n = int(g.shape[0])
    v = max(1, int(float(straggler.volume) * n))
    p = theory.wangni_probabilities(g, v)
    lhs, rhs = theory.check_convergence_condition(g, v, rho=0.5)
    return {"score_units": n, "volume": float(straggler.volume),
            "top_v": v,
            "variance_inflation": float(theory.variance_inflation(g, p)),
            "expected_sparsity": float(lhs), "eq9_bound": float(rhs),
            "eq9_holds": bool(float(lhs) <= float(rhs) + 1e-6)}


def table_scheme_gauntlet(model="lenet", rounds=12, nc=4, ns=4, seed=0,
                          out_path="BENCH_scheme_gauntlet.json"):
    """Every scheme in federated.schemes.SCHEMES — paper ablations AND the
    published straggler baselines (SCAFFOLD / FLuID / delayed-gradient) —
    under the IDENTICAL heterogeneous world: same non-IID partition, same
    half-straggler fleet, same seed.  Per scheme: the accuracy trajectory
    against SIMULATED wall-clock (each scheme's own round clock — syn
    waits for stragglers, delayed does not), total uplink bytes
    (scaffold's control variates ride dense at 2x), and for the
    soft-training schemes the Prop. 2 variance-inflation report at the
    straggler volumes the run settled on.  The JSON is the
    accuracy-vs-time-vs-uplink frontier the README table reads from.

    Engine per the scheme's own flag: async_native schemes run the
    bucketed event engine, everything else the batched sync engine.
    """
    import json

    cfg, imgs, labels, ti, tl, parts = _world(model, nc + ns, noniid=True,
                                              seed=seed)
    train = {"images": imgs, "labels": labels}
    test = {"images": ti, "labels": tl}
    results = {}
    for scheme in SCHEMES:
        sch = make_scheme(scheme)
        hcfg = HeliosConfig()
        clients = setup_clients(make_fleet(nc, ns), parts, hcfg)
        cls = AsyncFLRun if sch.async_native else BatchedFLRun
        run = cls(cfg, hcfg, scheme, clients, train, test,
                  local_steps=2, lr=0.02, seed=seed)
        if sch.async_native:
            # same capable-cycle budget convention as _run_scheme
            hist = run.run_async(rounds)
        else:
            hist = run.run_sync(rounds)
        rec = {
            "engine": cls.__name__,
            "final_acc": hist[-1]["acc"],
            "sim_time": hist[-1]["time"],
            "uplink_mb": run.uplink_bytes() / 1e6,
            "downlink_mb": run.downlink_bytes() / 1e6,
            "trajectory": [{"time": round(h["time"], 4),
                            "acc": round(h["acc"], 4),
                            "downlink_mb": round(h.get("downlink_mb", 0.0),
                                                 4)} for h in hist],
        }
        if sch.soft_training:
            strag = next(c for c in run.clients if c.is_straggler)
            rec["prop2"] = _prop2_report(strag)
        results[scheme] = rec
        extra = ""
        if "prop2" in rec:
            extra = (f";var_inflation={rec['prop2']['variance_inflation']:.3f}"
                     f";eq9={'ok' if rec['prop2']['eq9_holds'] else 'FAIL'}")
        emit(f"scheme_gauntlet/{model}/{scheme}",
             rec["sim_time"] / max(hist[-1]["cycle"], 1) * 1e6,
             f"acc={rec['final_acc']:.3f};simtime={rec['sim_time']:.2f};"
             f"uplink_mb={rec['uplink_mb']:.2f};"
             f"downlink_mb={rec['downlink_mb']:.2f}" + extra)
    with open(out_path, "w") as f:
        json.dump({"model": model, "rounds": rounds,
                   "fleet": {"capable": nc, "stragglers": ns},
                   "partition": "noniid", "seed": seed,
                   "local_steps": 2, "lr": 0.02,
                   "schemes": results,
                   "note": ("one world, every scheme: accuracy is at equal "
                            "ROUNDS; compare at equal sim_time for the "
                            "wall-clock frontier (each scheme's round "
                            "clock differs by design) and against "
                            "uplink_mb for the communication frontier; "
                            "prop2 rows price soft-training's gradient "
                            "variance (Eq. 6/9) at the settled volumes")},
                  f, indent=2)
    print(f"wrote {out_path}")


# ---------------------------------------------------------------------------
# batched round engine: rounds/sec, sequential vs vmapped cohorts
# ---------------------------------------------------------------------------


def _engine_throughput(tag, cfg, hcfg, train_data, test_data, parts_for,
                       counts, rounds, **run_kw):
    """Sequential-vs-batched rounds/sec over population sizes ``counts``.

    Shared by the CNN and LM throughput tables: warmup round (compile),
    timed eval-free window, per-count speedup rows via ``emit``.  Half the
    fleet are stragglers; ``parts_for(n)`` supplies the data partition.
    """
    results = []
    for n in counts:
        parts = parts_for(n)
        row = {"clients": n}
        for name, cls in (("sequential", FLRun), ("batched", BatchedFLRun)):
            clients = setup_clients(make_fleet(n - n // 2, n // 2), parts,
                                    hcfg)
            run = cls(cfg, hcfg, "helios", clients, train_data, test_data,
                      seed=0, **run_kw)
            run.run_sync(1, eval_every=0)                 # compile warmup
            jax.block_until_ready(run.global_params)
            t0 = time.perf_counter()
            run.run_sync(rounds, eval_every=0)            # no eval in window
            jax.block_until_ready(run.global_params)
            dt = time.perf_counter() - t0
            row[name] = {"rounds_per_sec": rounds / dt,
                         "sec_per_round": dt / rounds}
        row["speedup"] = (row["batched"]["rounds_per_sec"]
                          / row["sequential"]["rounds_per_sec"])
        emit(f"{tag}/{n}clients/sequential",
             row["sequential"]["sec_per_round"] * 1e6,
             f"rounds_per_sec={row['sequential']['rounds_per_sec']:.3f}")
        emit(f"{tag}/{n}clients/batched",
             row["batched"]["sec_per_round"] * 1e6,
             f"rounds_per_sec={row['batched']['rounds_per_sec']:.3f};"
             f"speedup_vs_sequential={row['speedup']:.2f}x")
        results.append(row)
    return results


def table_batched_rounds(model="lenet", counts=(16, 64, 256), rounds=3,
                         out_path="BENCH_batched_rounds.json"):
    """Round throughput at simulated-population scale.

    Cross-device regime: 1 local step, batch 16 per client, half the fleet
    stragglers.  The sequential engine pays O(clients) host dispatch + eager
    Helios state updates per round; the batched engine runs each round as
    one jitted vmapped program.  Results land in ``BENCH_batched_rounds.json``.
    """
    import json

    cfg = reduced(CNNS[model])
    noise = _NOISE.get(model, 4.0)
    imgs, labels = class_gaussian_images(
        2000, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=0,
        noise=noise)
    ti, tl = class_gaussian_images(
        256, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=99,
        noise=noise)
    run_kw = dict(local_steps=1, batch_size=16, lr=0.05)
    results = _engine_throughput(
        f"batched_rounds/{model}", cfg, HeliosConfig(),
        {"images": imgs, "labels": labels}, {"images": ti, "labels": tl},
        lambda n: partition_iid(len(labels), n, seed=0), counts, rounds,
        **run_kw)
    with open(out_path, "w") as f:
        json.dump({"model": model, "rounds": rounds, "scheme": "helios",
                   **run_kw, "results": results,
                   "contract_counters": dict(CT.counters)}, f, indent=2)
    print(f"wrote {out_path}")


# ---------------------------------------------------------------------------
# federated LM via the family-adapter seam: rounds/sec + CE trajectory
# ---------------------------------------------------------------------------


def table_federated_lm(arch="deepseek-7b", counts=(4, 8), rounds=3,
                       ce_rounds=4, out_path="BENCH_federated_lm.json"):
    """Federated LM round throughput, sequential vs batched engines.

    A reduced dense transformer trains on Non-IID Markov-topic token
    streams (partition_by_topic) with half the fleet stragglers; the CE
    trajectory (helios scheme, eval on the full test set per round) shows
    the LM actually learns through the soft-training path.  Results land in
    ``BENCH_federated_lm.json``.
    """
    import json

    from repro.configs import ARCHS
    from repro.data.federated import partition_by_topic
    from repro.data.synthetic import markov_topic_tokens

    cfg = reduced(ARCHS[arch])
    data_vocab = min(64, cfg.vocab_size)
    tokens, topics = markov_topic_tokens(768, 48, data_vocab,
                                         n_topics=8, seed=0)
    test_tokens, _ = markov_topic_tokens(128, 48, data_vocab,
                                         n_topics=8, seed=99)
    hcfg = HeliosConfig()
    train, test = {"tokens": tokens}, {"tokens": test_tokens}

    def parts_for(n):
        return partition_by_topic(topics, n, topics_per_client=2)

    tp_kw = dict(local_steps=1, batch_size=8, lr=0.1)
    results = _engine_throughput(f"federated_lm/{arch}", cfg, hcfg, train,
                                 test, parts_for, counts, rounds,
                                 eval_batch=64, **tp_kw)

    # CE trajectory: fresh batched run with full-test-set eval every round
    # (hotter hyperparameters than the throughput window — recorded as such)
    n = counts[0]
    ce_kw = dict(local_steps=4, batch_size=8, lr=0.5)
    clients = setup_clients(make_fleet(n - n // 2, n // 2), parts_for(n),
                            hcfg)
    run = BatchedFLRun(cfg, hcfg, "helios", clients, train, test, seed=0,
                       eval_batch=64, **ce_kw)
    hist = run.run_sync(ce_rounds)
    traj = [round(h["ce"], 4) for h in hist]
    emit(f"federated_lm/{arch}/{n}clients/ce_trajectory",
         hist[-1]["time"] / max(hist[-1]["cycle"], 1) * 1e6,
         "ce=" + "->".join(f"{c:.2f}" for c in traj))
    with open(out_path, "w") as f:
        json.dump({"arch": arch, "family": cfg.family, "scheme": "helios",
                   "data_vocab": data_vocab,
                   "uniform_ce": float(np.log(cfg.vocab_size)),
                   "throughput": {"rounds": rounds, **tp_kw,
                                  "results": results},
                   "ce": {"rounds": ce_rounds, "clients": n, **ce_kw,
                          "trajectory": traj}}, f, indent=2)
    print(f"wrote {out_path}")


# ---------------------------------------------------------------------------
# population-scale rounds: ShardedFLRun, partial participation, device sweep
# ---------------------------------------------------------------------------


def table_sharded_population(devices=(1, 2, 4, 8, 16),
                             populations=(256, 1024, 4096),
                             participation=32, rounds=15,
                             out_path="BENCH_sharded_population.json"):
    """Rounds/sec for the client-sharded population engine.

    Two axes, K=32 sampled per round throughout:
      * host devices 1 -> 16 at N=1024 (the shard_map scaling axis);
      * population N in {256, 1024, 4096} at the max device count (the
        persistent-population axis — rounds/sec must be ~N-independent,
        because only K rows ever move and data indexing is lazy).

    jax pins its device count at first init, so every cell runs in a
    SUBPROCESS with REPRO_HOST_DEVICES set (benchmarks/sharded_worker.py,
    the same forced-host-device pattern the dry-run tests validate).  Each
    worker asserts shape-stable compilation: exactly ONE compiled round
    program across all sampled cohorts after warmup.

    Device-sweep caveat recorded in the JSON: wall-clock scaling is bounded
    by PHYSICAL cores (a 1-device XLA CPU baseline already multi-threads),
    so on small containers the sweep validates overhead, not speedup.
    """
    import json

    def cell(n, dev):
        out = _run_worker(
            "benchmarks.sharded_worker",
            ["--population", str(n), "--participation", str(participation),
             "--rounds", str(rounds)],
            dict(os.environ, REPRO_HOST_DEVICES=str(dev)), timeout=1800)
        line = [ln for ln in out.splitlines()
                if ln.startswith("SHARDED ")][-1]
        rec = json.loads(line[len("SHARDED "):])
        assert rec["compiled_programs"] == 1, rec   # no recompile per draw
        emit(f"sharded_population/N={n}/dev={dev}",
             rec["sec_per_round"] * 1e6,
             f"rounds_per_sec={rec['rounds_per_sec']:.2f};"
             f"kpad={rec['kpad']};programs={rec['compiled_programs']}")
        return rec

    mid = populations[len(populations) // 2]
    sweep_dev = [cell(mid, d) for d in devices]
    sweep_pop = [cell(n, devices[-1]) for n in populations if n != mid]
    base = sweep_dev[0]["rounds_per_sec"]
    best = max(r["rounds_per_sec"] for r in sweep_dev)
    emit(f"sharded_population/N={mid}/device_sweep", 0.0,
         f"best_speedup_vs_1dev={best / base:.2f}x;"
         f"cpu_cores={os.cpu_count()}")
    with open(out_path, "w") as f:
        json.dump({
            "participation": participation, "rounds": rounds,
            "scheme": "helios", "sampler": "uniform",
            "host_cpu_count": os.cpu_count(),
            "device_sweep": sweep_dev,
            "population_sweep": sweep_pop,
            "best_speedup_vs_1dev": best / base,
            "note": ("device sweep is bounded by physical cores: the "
                     "1-device XLA CPU baseline already multi-threads "
                     "(cpu/wall ~1.4 on a 2-core host), so >=2x needs "
                     "cores >= shards; cohort-shape-stable padding holds "
                     "(compiled_programs == 1 in every cell)"),
        }, f, indent=2)
    print(f"wrote {out_path}")


# ---------------------------------------------------------------------------
# async events: sequential event loop vs bucketed AsyncFLRun, events/sec
# ---------------------------------------------------------------------------


def table_async_events(model="lenet", counts=(64, 256, 1024),
                       capable_per_client=1.0,
                       out_path="BENCH_async_events.json"):
    """Events/sec for the async schemes (afo), half-straggler fleets.

    The sequential reference dispatches one jitted client cycle + a
    host-dict snapshot per completion event — O(events) host overhead.
    The bucketed engine executes each equal-time tie-group as ONE vmapped
    program reading/writing a device snapshot ring, so host dispatch is
    O(buckets).  Both engines process the IDENTICAL event set for a fixed
    seed (tests/test_async_engine.py pins the trajectories), which makes
    events/sec an apples-to-apples execution-layer number.  Data partitions
    are lazy non-IID (partition_noniid_lazy): no N per-client index arrays.
    """
    import json

    cfg = reduced(CNNS[model])
    noise = _NOISE.get(model, 4.0)
    imgs, labels = class_gaussian_images(
        4096, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=0,
        noise=noise)
    ti, tl = class_gaussian_images(
        128, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=99,
        noise=noise)
    train, test = {"images": imgs, "labels": labels}, \
        {"images": ti, "labels": tl}
    hcfg = HeliosConfig()
    run_kw = dict(local_steps=1, batch_size=16, lr=0.05, seed=0)
    results = []
    for n in counts:
        parts = partition_noniid_lazy(labels, n, shards_per_client=4,
                                      seed=0)
        capable = max(16, int(n * capable_per_client))
        row = {"clients": n, "capable_cycles": capable}
        for name, cls in (("sequential", FLRun), ("bucketed", AsyncFLRun)):
            clients = setup_clients(make_fleet(n - n // 2, n // 2), parts,
                                    hcfg)
            run = cls(cfg, hcfg, "afo", clients, train, test, **run_kw)
            # warmup over the SAME capable budget: the event schedule is
            # deterministic from t=0, so this visits exactly the bucket
            # shapes the timed window will, compiling all of them up front
            run.run_async(capable, eval_every=0)
            jax.block_until_ready(run.global_params)
            t0 = time.perf_counter()
            run.run_async(capable, eval_every=0)
            jax.block_until_ready(run.global_params)
            dt = time.perf_counter() - t0
            row[name] = {"events": run.events_processed,
                         "seconds": dt,
                         "events_per_sec": run.events_processed / dt}
            if name == "bucketed":
                progs = run.bucket_programs()
                # shape-stable: one compile per padded bucket size
                assert all(v == 1 for v in progs.values()), progs
                row[name]["bucket_programs"] = {str(k): v
                                                for k, v in progs.items()}
                row[name]["mean_bucket"] = float(np.mean(run.bucket_sizes))
        row["speedup"] = (row["bucketed"]["events_per_sec"]
                          / row["sequential"]["events_per_sec"])
        emit(f"async_events/{model}/{n}clients/sequential",
             1e6 / row["sequential"]["events_per_sec"],
             f"events_per_sec={row['sequential']['events_per_sec']:.1f}")
        emit(f"async_events/{model}/{n}clients/bucketed",
             1e6 / row["bucketed"]["events_per_sec"],
             f"events_per_sec={row['bucketed']['events_per_sec']:.1f};"
             f"speedup_vs_sequential={row['speedup']:.2f}x;"
             f"mean_bucket={row['bucketed']['mean_bucket']:.1f}")
        results.append(row)
    with open(out_path, "w") as f:
        json.dump({"model": model, "scheme": "afo",
                   "partition": "noniid_lazy", **run_kw,
                   "results": results,
                   "contract_counters": dict(CT.counters)}, f, indent=2)
    print(f"wrote {out_path}")


# ---------------------------------------------------------------------------
# runtime contracts: guard overhead, off vs on
# ---------------------------------------------------------------------------


def table_contracts_overhead(model="lenet", n_clients=8, rounds=6,
                             out_path="BENCH_contracts.json"):
    """repro.analysis.contracts cost on the batched engine, off vs on.

    Same seed/fleet/trajectory both ways; ``off`` is the default CI/bench
    mode and must be genuinely free — no guard installed, every counter
    still zero after the run (asserted and recorded).  ``on`` pays the
    transfer-guard sections plus the per-run finite/mask/compile checks;
    the JSON records the counter census so regressions in check volume
    are visible, not just wall time.
    """
    import json

    cfg = reduced(CNNS[model])
    noise = _NOISE.get(model, 4.0)
    imgs, labels = class_gaussian_images(
        1024, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=0,
        noise=noise)
    ti, tl = class_gaussian_images(
        128, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=99,
        noise=noise)
    parts = partition_iid(len(labels), n_clients, seed=0)
    run_kw = dict(local_steps=1, batch_size=16, lr=0.05, seed=0)
    results = {}
    for mode in ("off", "on"):
        CT.reset_counters()
        clients = setup_clients(make_fleet(n_clients - n_clients // 2,
                                           n_clients // 2), parts,
                                HeliosConfig())
        run = BatchedFLRun(cfg, HeliosConfig(), "helios", clients,
                           {"images": imgs, "labels": labels},
                           {"images": ti, "labels": tl}, **run_kw)
        with CT.override(mode == "on"):
            run.run_sync(1, eval_every=0)                 # compile warmup
            jax.block_until_ready(run.global_params)
            t0 = time.perf_counter()
            run.run_sync(rounds, eval_every=0)
            jax.block_until_ready(run.global_params)
            dt = time.perf_counter() - t0
        results[mode] = {"sec_per_round": dt / rounds,
                         "rounds_per_sec": rounds / dt,
                         "counters": dict(CT.counters)}
    off, on = results["off"], results["on"]
    assert all(v == 0 for v in off["counters"].values()), off["counters"]
    overhead = on["sec_per_round"] / off["sec_per_round"] - 1.0
    emit(f"contracts/{model}/{n_clients}clients/off",
         off["sec_per_round"] * 1e6,
         f"rounds_per_sec={off['rounds_per_sec']:.3f}")
    emit(f"contracts/{model}/{n_clients}clients/on",
         on["sec_per_round"] * 1e6,
         f"rounds_per_sec={on['rounds_per_sec']:.3f};"
         f"overhead={overhead * 100:+.1f}%;"
         f"checks={sum(on['counters'].values())}")
    with open(out_path, "w") as f:
        json.dump({"model": model, "clients": n_clients, "rounds": rounds,
                   "scheme": "helios", **{k: v for k, v in run_kw.items()
                                          if k != "seed"},
                   "results": results, "overhead_frac": overhead}, f,
                  indent=2)
    print(f"wrote {out_path}")


# ---------------------------------------------------------------------------
# observability: telemetry cost on the batched engine, off vs on
# ---------------------------------------------------------------------------


def table_observability(model="lenet", n_clients=8, rounds=6, reps=3,
                        out_path="BENCH_observability.json",
                        run_dir="obs_run"):
    """repro.obs telemetry cost on the batched engine, off vs on (the
    table_contracts_overhead pattern, applied to the other arming seam).

    Same seed/fleet/trajectory both ways.  ``off`` is the default mode:
    the recorder still does the engine's accounting (counters/accums are
    the bookkeeping itself) but must buffer ZERO events (asserted and
    recorded).  ``on`` pays span/event emission inside the round loop;
    the timed window is eval-free so ``overhead_frac`` prices telemetry
    alone.  The armed run then takes two evaluated rounds (untimed, both
    modes, so trajectories stay comparable) and flushes its run log to
    ``run_dir`` — the input for ``python -m repro.obs report``.  The JSON
    carries the armed run's manifest and a run-log-shaped ``summary``
    block so ``python -m repro.obs diff`` compares this bench file and a
    fresh run log uniformly.
    """
    import json

    from repro.obs import recorder as OBS
    from repro.obs import report as OBR

    cfg = reduced(CNNS[model])
    noise = _NOISE.get(model, 4.0)
    imgs, labels = class_gaussian_images(
        1024, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=0,
        noise=noise)
    ti, tl = class_gaussian_images(
        128, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=99,
        noise=noise)
    parts = partition_iid(len(labels), n_clients, seed=0)
    run_kw = dict(local_steps=1, batch_size=16, lr=0.05, seed=0)
    runs, best = {}, {}
    for mode in ("off", "on"):
        clients = setup_clients(make_fleet(n_clients - n_clients // 2,
                                           n_clients // 2), parts,
                                HeliosConfig())
        with OBS.override(mode == "on"):
            # the recorder arms at construction, so the run is built
            # inside the override (exactly how REPRO_OBS=on would see it)
            run = BatchedFLRun(cfg, HeliosConfig(), "helios", clients,
                               {"images": imgs, "labels": labels},
                               {"images": ti, "labels": tl}, **run_kw)
        run.run_sync(1, eval_every=0)                     # compile warmup
        jax.block_until_ready(run.global_params)
        runs[mode], best[mode] = run, float("inf")
    # interleaved min-of-reps laps: the eval-free window is short
    # (~rounds x tens of ms), so back-to-back off-then-on measurement
    # would fold host frequency drift into the overhead number
    for _ in range(reps):
        for mode, run in runs.items():
            t0 = time.perf_counter()
            run.run_sync(rounds, eval_every=0)
            jax.block_until_ready(run.global_params)
            best[mode] = min(best[mode], time.perf_counter() - t0)
    results = {}
    for mode, run in runs.items():
        hist = run.run_sync(2, eval_every=1)              # untimed, w/ eval
        results[mode] = {"sec_per_round": best[mode] / rounds,
                         "rounds_per_sec": rounds / best[mode],
                         "events": len(run.rec.events),
                         "counters": dict(run.rec.counters)}
    assert not runs["off"].rec.armed and not runs["off"].rec.events, \
        "disarmed recorder buffered events"
    armed_run, armed_hist = runs["on"], hist
    off, on = results["off"], results["on"]
    overhead = on["sec_per_round"] / off["sec_per_round"] - 1.0
    emit(f"observability/{model}/{n_clients}clients/off",
         off["sec_per_round"] * 1e6,
         f"rounds_per_sec={off['rounds_per_sec']:.3f};events=0")
    emit(f"observability/{model}/{n_clients}clients/on",
         on["sec_per_round"] * 1e6,
         f"rounds_per_sec={on['rounds_per_sec']:.3f};"
         f"overhead={overhead * 100:+.1f}%;events={on['events']}")
    flushed = armed_run.rec.flush(run_dir)
    print(f"wrote {flushed['events']}")
    summary = OBR.summarize(
        OBR.load_events(os.path.join(run_dir, "events.jsonl")))
    with open(out_path, "w") as f:
        json.dump({"model": model, "clients": n_clients, "rounds": rounds,
                   "scheme": "helios",
                   **{k: v for k, v in run_kw.items() if k != "seed"},
                   "results": results, "overhead_frac": overhead,
                   "final_acc": armed_hist[-1]["acc"],
                   "manifest": dict(armed_run.rec.manifest),
                   "summary": summary}, f, indent=2)
    print(f"wrote {out_path}")


# ---------------------------------------------------------------------------
# serve-while-you-train: Poisson traffic against the live global model
# ---------------------------------------------------------------------------


def table_serve_traffic(arch="deepseek-7b", n_clients=4, rounds=4,
                        rate_hz=20.0, batch=4, prompt_len=16, gen=4,
                        kernels="reference", max_requests=200,
                        out_path="BENCH_serve_traffic.json",
                        run_dir="obs_serve"):
    """The first bench that measures the system as a SERVICE: batched
    generation traffic served against the live global model while a
    `BatchedFLRun` trains concurrently in the same process.

    The training thread publishes atomic snapshots every round
    (``publish_dir``); the serving thread polls them behind the
    eval-gated promotion rule and hot-swaps lock-free (params are a
    traced argument, so ``GenerationServer`` keeps ONE compiled
    prefill + ONE decode program across every swap — asserted).  Load
    is an open-loop Poisson arrival schedule (fixed by seed): latency
    per request is completion minus SCHEDULED arrival, so queueing
    delay under overload is priced in rather than the arrival process
    quietly slowing down, and a decode's intermediate steps stay
    async-dispatched — each request blocks once, on its own response.
    Both planes share one armed recorder, flushed to ``run_dir`` for
    ``python -m repro.obs report``.
    """
    import json
    import tempfile

    from repro import checkpoint as CKPT
    from repro.configs import ARCHS
    from repro.data.federated import partition_by_topic
    from repro.data.synthetic import markov_tokens, markov_topic_tokens
    from repro.launch.serve import (GenerationServer, PoissonTraffic,
                                    ServeLoop, make_ce_eval, serve_batch,
                                    serve_while_training)
    from repro.models import init_params
    from repro.obs import recorder as OBS
    from repro.obs import report as OBR

    cfg = reduced(ARCHS[arch])
    data_vocab = min(64, cfg.vocab_size)
    tokens, topics = markov_topic_tokens(256, 32, data_vocab,
                                         n_topics=8, seed=0)
    test_tokens, _ = markov_topic_tokens(64, 32, data_vocab,
                                         n_topics=8, seed=99)
    parts = partition_by_topic(topics, n_clients, topics_per_client=2)
    hcfg = HeliosConfig()
    clients = setup_clients(make_fleet(n_clients - n_clients // 2,
                                       n_clients // 2), parts, hcfg)
    rec = OBS.Recorder(armed=True)
    pub = tempfile.mkdtemp(prefix="serve_pub_")
    run_kw = dict(local_steps=2, batch_size=8, lr=0.1, seed=0,
                  eval_batch=64)
    run = BatchedFLRun(cfg, hcfg, "helios", clients, {"tokens": tokens},
                       {"tokens": test_tokens}, recorder=rec,
                       publish_dir=pub, publish_every=1, **run_kw)

    srv = GenerationServer(cfg, batch, prompt_len, gen=gen, kernels=kernels)
    held = {"tokens": jnp.asarray(test_tokens[:32])}
    serve = ServeLoop(pub, init_params(jax.random.PRNGKey(0), cfg),
                      request_fn=srv, eval_fn=make_ce_eval(cfg, held),
                      higher_is_better=False, tol=0.05, recorder=rec)
    # round 0 snapshot: traffic has something to serve from request one
    CKPT.save(pub, 0, run.global_params, keep=run.publish_keep,
              metadata={"round": 0, "sim_time": 0.0, "scheme": run.scheme})
    assert serve.poll(), "initial snapshot must promote"
    prompts = markov_tokens(batch, prompt_len, cfg.padded_vocab, seed=7)
    req = serve_batch(cfg, prompts, np.random.default_rng(7))
    serve.handle(req)                      # compile warmup, untimed
    traffic = PoissonTraffic(rate_hz=rate_hz, seed=0)
    stats = serve_while_training(lambda: run.run_sync(rounds),
                                 serve, traffic, lambda i: req,
                                 min_requests=10, max_requests=max_requests)

    assert srv.programs() == {"prefill": 1, "decode": 1}, \
        f"hot swap recompiled the serving path: {srv.programs()}"
    swaps = rec.count("serve_swaps")
    assert swaps >= 1 and rec.count("published_snapshots") == rounds
    lat = sorted(stats["latency_ms"])
    n = len(lat)
    p50, p99 = lat[n // 2], lat[min((99 * n) // 100, n - 1)]
    emit(f"serve_traffic/{arch}/{rate_hz:g}hz/{kernels}",
         stats["wall_s"] / max(stats["requests"], 1) * 1e6,
         f"req_per_sec={stats['requests_per_sec']:.1f};"
         f"p50={p50:.1f}ms;p99={p99:.1f}ms;swaps={swaps}")
    flushed = rec.flush(run_dir)
    print(f"wrote {flushed['events']}")
    summary = OBR.summarize(
        OBR.load_events(os.path.join(run_dir, "events.jsonl")))
    with open(out_path, "w") as f:
        json.dump({"arch": arch, "clients": n_clients, "rounds": rounds,
                   "scheme": "helios", "kernels": kernels,
                   "batch": batch, "prompt_len": prompt_len, "gen": gen,
                   **{k: v for k, v in run_kw.items() if k != "seed"},
                   "results": {
                       "requests": stats["requests"],
                       "wall_s": stats["wall_s"],
                       "requests_per_sec": stats["requests_per_sec"],
                       "offered_rate_hz": stats["offered_rate_hz"],
                       "p50_ms": p50, "p99_ms": p99,
                       "swaps": swaps,
                       "promotions": rec.count("serve_promotions"),
                       "rejections": rec.count("serve_rejections"),
                       "published": rec.count("published_snapshots"),
                       "served_step": serve.served_step,
                       "served_round": serve.served_round},
                   "programs": srv.programs(),
                   "manifest": dict(rec.manifest),
                   "summary": summary}, f, indent=2)
    print(f"wrote {out_path}")


# ---------------------------------------------------------------------------
# kernels: wall time + oracle error (CPU interpret)
# ---------------------------------------------------------------------------


def bench_kernels():
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.masked_matmul import masked_matmul

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 512))
    w = jax.random.normal(jax.random.fold_in(key, 1), (512, 1024))
    for frac, alive in (("dense", jnp.ones(8, bool)),
                        ("quarter", (jnp.arange(8) < 2))):
        f = lambda: masked_matmul(x, w, alive, interpret=True)
        out = f()
        out.block_until_ready()
        t0 = time.time()
        for _ in range(3):
            f().block_until_ready()
        us = (time.time() - t0) / 3 * 1e6
        err = float(jnp.max(jnp.abs(
            out - ref.masked_matmul_ref(x, w, alive, 128))))
        emit(f"kernel/masked_matmul/{frac}", us, f"max_err={err:.2e}")

    q = jax.random.normal(key, (1, 4, 256, 64))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 4, 256, 64))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 4, 256, 64))
    f = lambda: flash_attention(q, k, v, causal=True, interpret=True)
    out = f()
    out.block_until_ready()
    t0 = time.time()
    for _ in range(3):
        f().block_until_ready()
    us = (time.time() - t0) / 3 * 1e6
    err = float(jnp.max(jnp.abs(out - ref.flash_attention_ref(q, k, v))))
    emit("kernel/flash_attention/256", us, f"max_err={err:.2e}")


# ---------------------------------------------------------------------------
# kernel-backed soft-training: tokens/sec vs volume fraction P
# ---------------------------------------------------------------------------


def table_kernel_softtrain(fracs=(0.25, 0.5, 0.75, 1.0), steps=4,
                           out_path="BENCH_kernel_softtrain.json"):
    """Soft-training step throughput, reference (plain jnp masked ops) vs
    pallas (block-sparse masked-matmul pair + flash attention), as the
    volume fraction P sweeps the Helios straggler range.

    One jitted train step per substrate serves EVERY P (masks are traced
    0/1 inputs, block-aligned at mask_block=128) — asserted via the jit
    cache size, so the adaptive volume controller never pays a recompile.
    On this CPU container the pallas path runs in interpret mode (the
    kernel body as traced JAX ops): the numbers validate dispatch overhead
    and P-scaling plumbing, NOT kernel wall-clock — the dead-block skip
    turns into real speedup on TPU hosts where the kernels compile natively.
    """
    import json

    from repro.configs.base import ModelConfig
    from repro.kernels.ops import block_align_mask
    from repro.models import build, default_runtime, init_params

    cfg = ModelConfig(name="bench-dense", family="dense", num_layers=2,
                      d_model=128, num_heads=4, num_kv_heads=4, d_ff=512,
                      vocab_size=256, head_dim=32)
    api = build(cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 8, 128
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S),
                                          0, 64)}
    schema = api.mask_schema                   # {"heads": (L,H), "mlp": (L,ff)}

    def masks_at(frac):
        out = {}
        for key, (L, n) in schema.items():
            if key == "mlp":
                m = (jnp.arange(n) < max(1, int(frac * n))).astype(jnp.float32)
                m = block_align_mask(m, 128)
                out[key] = jnp.broadcast_to(m, (L, n))
            else:
                out[key] = jnp.ones((L, n), jnp.float32)
        return out

    results = {f: {} for f in fracs}
    compiled = {}
    for impl in ("reference", "pallas"):
        rt = default_runtime(cfg)
        rt["kernels"] = impl
        rt["mask_block"] = 128
        # the python body runs once per TRACE, so this counts compiles
        # without reaching into jit internals
        traces = {"n": 0}

        @jax.jit
        def step(p, masks, rt=rt, traces=traces):
            traces["n"] += 1
            loss, g = jax.value_and_grad(
                lambda pp: api.loss_fn(pp, batch, cfg, rt, masks))(p)
            return jax.tree.map(lambda a, b: a - 0.01 * b, p, g), loss

        for frac in fracs:
            masks = masks_at(frac)
            p = params
            p, _ = step(p, masks)              # warmup (first P compiles)
            jax.block_until_ready(jax.tree.leaves(p)[0])
            t0 = time.perf_counter()
            for _ in range(steps):
                p, loss = step(p, masks)
            jax.block_until_ready(jax.tree.leaves(p)[0])
            dt = time.perf_counter() - t0
            tps = B * S * steps / dt
            results[frac][impl] = {"tokens_per_sec": tps,
                                   "sec_per_step": dt / steps,
                                   "loss": float(loss)}
        # ONE program per substrate across the whole P sweep: volume changes
        # are traced mask values, never new shapes
        compiled[impl] = traces["n"]
        assert compiled[impl] == 1, (impl, compiled[impl])

    rows = []
    for frac in fracs:
        r = results[frac]
        ratio = (r["pallas"]["tokens_per_sec"]
                 / r["reference"]["tokens_per_sec"])
        rows.append({"P": frac, **r, "pallas_vs_reference": ratio})
        emit(f"kernel_softtrain/P={frac}/reference",
             r["reference"]["sec_per_step"] * 1e6,
             f"tokens_per_sec={r['reference']['tokens_per_sec']:.0f}")
        emit(f"kernel_softtrain/P={frac}/pallas",
             r["pallas"]["sec_per_step"] * 1e6,
             f"tokens_per_sec={r['pallas']['tokens_per_sec']:.0f};"
             f"vs_reference={ratio:.2f}x")
    with open(out_path, "w") as f:
        json.dump({
            "model": {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
                      "num_layers": cfg.num_layers, "heads": cfg.num_heads},
            "batch": B, "seq": S, "steps": steps, "mask_block": 128,
            "backend": jax.default_backend(),
            "interpret": jax.default_backend() == "cpu",
            "compiled_programs": compiled,
            "results": rows,
            "note": ("CPU cells run the Pallas kernels in interpret mode — "
                     "they pin numerics and shape-stable dispatch (one "
                     "compiled step per substrate across all P), not wall "
                     "clock; the block-skip FLOP win needs a TPU host "
                     "(native pallas_call)."),
        }, f, indent=2)
    print(f"wrote {out_path}")


# ---------------------------------------------------------------------------
# TPU-native soft-training: compiled FLOP reduction (cost_analysis)
# ---------------------------------------------------------------------------


def bench_softtrain_flops():
    """compact (gathered) MLP vs full MLP: the compiled FLOPs shrink ~P —
    the paper's straggler acceleration mechanism on the MXU."""
    from repro.models.layers import mlp_fwd, mlp_spec
    from repro.models.module import init_params

    d, ff = 512, 2048
    spec = mlp_spec(d, ff, "silu")
    params = init_params(jax.random.PRNGKey(0), spec)
    x = jnp.ones((64, 128, d))

    full = jax.jit(lambda p, x: mlp_fwd(p, x, "silu")).lower(
        params, x).compile()
    base = full.cost_analysis()["flops"]
    for pfrac in (0.5, 0.25):
        k = int(ff * pfrac)
        idx = jnp.arange(k, dtype=jnp.int32)
        comp = jax.jit(lambda p, x, i: mlp_fwd(p, x, "silu", active_idx=i)
                       ).lower(params, x, idx).compile()
        flops = comp.cost_analysis()["flops"]
        emit(f"softtrain/compact_mlp/P={pfrac}", 0.0,
             f"flop_fraction={flops / base:.3f}")


def table_million_population(populations=(10_000, 100_000, 1_000_000),
                             participation=64, rounds=3,
                             modes=("none", "topk", "quant", "delta"),
                             conv_rounds=12,
                             host_budget_bytes=16 * 1024 ** 3,
                             out_path="BENCH_million_population.json"):
    """Million-client populations under a stated host-memory budget.

    One subprocess per (N, mode) cell (benchmarks/million_worker.py):
    sharded engine, K=64 sampled clients/round, uplink compression at the
    aggregation boundary.  Reported against the STATED budget
    (``host_budget_bytes``, default 16 GiB): peak host RSS over the whole
    worker lifetime (population setup included), uplink bytes/round, and
    rounds/sec.  Warmup round runs outside the timed window (same
    discipline as the async bench).  Every cell asserts shape-stable
    compilation and peak RSS under budget; the topk cells must clear the
    >= 10x uplink reduction the compression layer exists for.

    A small in-process convergence table (full participation, N=8,
    ``conv_rounds`` rounds) records the final metric of every lossy mode
    against ``none`` — the accuracy price of each wire format.
    """
    import json

    def cell(n, mode):
        out = _run_worker(
            "benchmarks.million_worker",
            ["--population", str(n), "--participation", str(participation),
             "--rounds", str(rounds), "--mode", mode],
            dict(os.environ), timeout=3600)
        line = [ln for ln in out.splitlines()
                if ln.startswith("MILLION ")][-1]
        rec = json.loads(line[len("MILLION "):])
        assert rec["compiled_programs"] == 1, rec   # no recompile per draw
        assert rec["peak_host_bytes"] < host_budget_bytes, rec
        rec["within_budget"] = True
        emit(f"million_population/N={n}/{mode}",
             rec["sec_per_round"] * 1e6,
             f"rounds_per_sec={rec['rounds_per_sec']:.2f};"
             f"peak_gb={rec['peak_host_bytes'] / 1024 ** 3:.2f};"
             f"uplink_mb_per_round="
             f"{rec['uplink_bytes_per_round'] / 1e6:.2f}")
        return rec

    cells = [cell(n, mode) for n in populations for mode in modes]
    by = {(r["population"], r["mode"]): r for r in cells}
    n_max = max(populations)
    reduction = {m: by[(n_max, "none")]["uplink_bytes_per_round"]
                 / by[(n_max, m)]["uplink_bytes_per_round"]
                 for m in modes if m != "none"}
    assert reduction.get("topk", 10.0) >= 10.0, reduction
    emit(f"million_population/N={n_max}/uplink_reduction", 0.0,
         ";".join(f"{m}={x:.1f}x" for m, x in sorted(reduction.items())))

    # convergence delta: the accuracy price of each wire format
    cfg = reduced(CNNS["lenet"])
    imgs, labels = class_gaussian_images(
        800, cfg.image_size, cfg.in_channels, cfg.num_classes, seed=0)
    ti, tl = class_gaussian_images(128, cfg.image_size, cfg.in_channels,
                                   cfg.num_classes, seed=9)
    parts = partition_iid(len(labels), 8, seed=0)
    conv = {}
    for mode in modes:
        hcfg = HeliosConfig()
        clients = setup_clients(make_fleet(4, 4), parts, hcfg)
        run = BatchedFLRun(cfg, hcfg, "helios", clients,
                           {"images": imgs, "labels": labels},
                           {"images": ti, "labels": tl},
                           local_steps=1, batch_size=16, lr=0.1, seed=0,
                           eval_batch=128, compression=mode)
        run.run_sync(conv_rounds, eval_every=0)
        conv[mode] = {"final_accuracy": run.evaluate(),
                      "uplink_bytes": run.uplink_bytes()}
    for mode in modes:
        conv[mode]["delta_vs_none"] = (conv[mode]["final_accuracy"]
                                       - conv["none"]["final_accuracy"])
        emit(f"million_population/convergence/{mode}", 0.0,
             f"acc={conv[mode]['final_accuracy']:.4f};"
             f"delta={conv[mode]['delta_vs_none']:+.4f}")

    with open(out_path, "w") as f:
        json.dump({
            "participation": participation, "rounds": rounds,
            "scheme": "helios", "host_budget_bytes": host_budget_bytes,
            "host_cpu_count": os.cpu_count(),
            "cells": cells,
            "uplink_reduction_at_max_n": reduction,
            "convergence": {"rounds": conv_rounds, "clients": 8,
                            "table": conv},
            "note": ("peak_host_bytes is worker-process ru_maxrss "
                     "(population setup included); uplink bytes follow "
                     "the wire formats in optim/compression.py "
                     "(fp16 values for topk, int codes + per-leaf "
                     "scales for quant/delta); error-feedback rows "
                     "materialize host-side only for clients that have "
                     "participated"),
        }, f, indent=2)
    print(f"wrote {out_path}")


TABLES = {
    "fig5": table_convergence,
    "speedup": table_speedup,
    "fig6": table_aggregation_opt,
    "fig7": table_noniid,
    "ablation": table_ps_ablation,
    "scheme_gauntlet": table_scheme_gauntlet,
    "batched": table_batched_rounds,
    "federated_lm": table_federated_lm,
    "sharded_population": table_sharded_population,
    "million_population": table_million_population,
    "async_events": table_async_events,
    "contracts": table_contracts_overhead,
    "observability": table_observability,
    "serve_traffic": table_serve_traffic,
    "kernel_softtrain": table_kernel_softtrain,
    "kernels": bench_kernels,
    "softtrain": bench_softtrain_flops,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args, _ = ap.parse_known_args()
    xla_env.use_compile_cache()

    only = args.only.split(",") if args.only else list(TABLES)
    for name in only:
        fn = TABLES[name]
        print(f"## {name}", flush=True)
        if args.quick and name == "fig5":
            fn(models=("lenet",), rounds=6)
        elif args.quick and name in ("speedup", "fig6", "fig7"):
            fn(rounds=6)
        elif args.quick and name == "scheme_gauntlet":
            fn(rounds=3)
        elif args.quick and name == "batched":
            fn(counts=(16, 64), rounds=2)
        elif args.quick and name == "federated_lm":
            fn(counts=(4,), rounds=2, ce_rounds=2)
        elif args.quick and name == "sharded_population":
            fn(devices=(1, 16), populations=(256,), rounds=4)
        elif args.quick and name == "million_population":
            fn(populations=(4096,), participation=32, rounds=2,
               conv_rounds=4)
        elif args.quick and name == "async_events":
            fn(counts=(64,), capable_per_client=0.5)
        elif args.quick and name == "contracts":
            fn(n_clients=4, rounds=3)
        elif args.quick and name == "observability":
            fn(n_clients=4, rounds=3, reps=2)
        elif args.quick and name == "serve_traffic":
            fn(rounds=2, rate_hz=50.0, max_requests=40)
        elif args.quick and name == "kernel_softtrain":
            fn(fracs=(0.25, 1.0), steps=2)
        else:
            fn()
    print(f"\n{len(ROWS)} rows")


if __name__ == "__main__":
    main()
