"""Federated round engines: execution strategies for any Scheme.

The ALGORITHM lives behind the pluggable policy seam in
:mod:`repro.federated.schemes` — the paper's helios / syn / st_only /
random / asyn / afo plus the published straggler baselines scaffold /
fluid / delayed.  This module owns EXECUTION only: an engine never
compares scheme strings (tests/test_schemes.py asserts that), it reads
the resolved ``self._scheme`` policy object's flags and hooks.

Time is simulated (federated.events / heterogeneity.cycle_time); the metric
is real (models train on real arrays).  The engines are FAMILY-BLIND and
SCHEME-BLIND: everything that varies by model family lives behind
federated.adapter.FamilyAdapter and everything that varies by algorithm
behind federated.schemes.Scheme, so the same engines federate the CNN
testbed and the token-stream LM families under any registered scheme.
Train/test data are dicts of aligned arrays keyed like the model's batch,
indexed along axis 0.

The engine matrix (one execution strategy per row, same semantics per
column):

  * :class:`FLRun` — the sequential reference for BOTH timing models: the
    sync loop re-dispatches ``_local_train`` per client, and ``run_async``
    processes one completion event at a time with Python-dict snapshots.
    Simple, but host dispatch caps the simulated population size.
  * :class:`AsyncFLRun` — the bucketed async engine: the deterministic
    event core (federated.events) pops buckets of near-simultaneous
    completions and each bucket runs as ONE jitted program (vmapped local
    training from a device-side snapshot ring + staleness-weighted mixing
    scan).  Same seed => same trajectory as ``FLRun.run_async``.
  * :class:`BatchedFLRun` — the batched sync engine: a whole round
    (begin_cycle -> masked training -> end_cycle -> aggregation) as one
    jitted vmapped program per cohort.  Inherits the bucketed async path.
  * :class:`ShardedFLRun` — the batched round program shard_mapped over a
    1-D ``("clients",)`` device mesh with host-resident population state.

All four sync loops share ONE host protocol — the template method
:meth:`FLRun.run_sync` (draw cohort -> pace -> times -> train -> volume
adaptation -> record); engines override the ``_train_cohort`` /
``_write_volumes`` / ``_finish_sync`` hooks, never the loop, so the
cross-engine equivalence contract is stated in exactly one place.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro import checkpoint as CKPT
from repro.analysis import contracts as CT
from repro.configs.base import HeliosConfig, ModelConfig
from repro.core import aggregation as AG
from repro.core import masking as MK
from repro.core import soft_train as ST
from repro.core import volume as VOL
from repro.core.identification import (DeviceProfile, identify_resource_based,
                                       identify_time_based)
from repro.federated.adapter import (DeviceData, FamilyAdapter,
                                     device_bytes_limit, fits_on_device,
                                     make_adapter)
from repro.federated.events import (ArrivalProcess, DropoutProcess, Event,
                                    SimClock)
from repro.federated.heterogeneity import cycle_time
from repro.federated.schemes import Scheme, make_scheme
from repro.launch.mesh import make_client_mesh
from repro.models import init_params
from repro.obs import recorder as OBS
from repro.optim import apply_updates, compression as CP, make_optimizer


def _make_local_train(adapter: FamilyAdapter, opt, with_correction=False):
    """E masked local SGD steps under lax.scan — the one training loop all
    engines share (sequential jits it directly; batched/async engines vmap
    it per cohort/bucket, which keeps the engines numerically in
    lock-step).  ``batches`` is a dict pytree whose leaves carry a leading
    (local_steps,) axis.

    ``with_correction`` (SCAFFOLD schemes) adds a fixed per-client
    gradient correction ``corr = c_global - c_i`` to every step — a
    fourth argument, built only when the scheme asks so every other
    scheme's program signature is byte-identical to the pre-seam one."""

    if with_correction:
        def local_train_corr(params, batches, masks, corr):
            opt_state = opt.init(params)

            def step(carry, batch):
                p, s = carry

                def loss_fn(pp):
                    return adapter.loss_fn(pp, batch, masks)

                loss, grads = jax.value_and_grad(loss_fn)(p)
                grads = jax.tree.map(lambda g, c: g + c, grads, corr)
                updates, s = opt.update(grads, s, p, 0)
                return (apply_updates(p, updates), s), loss

            (params, _), losses = jax.lax.scan(step, (params, opt_state),
                                               batches)
            return params, losses.mean()

        return local_train_corr

    def local_train(params, batches, masks):
        opt_state = opt.init(params)

        def step(carry, batch):
            p, s = carry

            def loss_fn(pp):
                return adapter.loss_fn(pp, batch, masks)

            loss, grads = jax.value_and_grad(loss_fn)(p)
            updates, s = opt.update(grads, s, p, 0)
            return (apply_updates(p, updates), s), loss

        (params, _), losses = jax.lax.scan(step, (params, opt_state), batches)
        return params, losses.mean()

    return local_train


def _median_pace(capable_times: Sequence[float]) -> float:
    """Median capable-device cycle time, 1.0 for an all-straggler cohort.

    The explicit empty guard matters: ``np.median([])`` is NaN, and NaN is
    truthy, so ``float(np.median([...])) or 1.0`` silently kept NaN and
    poisoned the volume controller.
    """
    return float(np.median(capable_times)) if capable_times else 1.0


def _collab_pace(clients: Sequence["Client"]) -> float:
    """§IV.C collaboration pace over a client list."""
    return _median_pace([cycle_time(c.profile, 1.0) for c in clients
                         if not c.is_straggler])


@dataclasses.dataclass
class Client:
    cid: int
    profile: DeviceProfile
    data_idx: np.ndarray
    volume: float = 1.0
    helios_state: Optional[dict] = None
    is_straggler: bool = False
    staleness_anchor: int = 0          # agg step the client last pulled from


@dataclasses.dataclass
class FLRun:
    """One engine execution: holds jitted steps + mutable server state."""

    cfg: ModelConfig
    hcfg: HeliosConfig
    scheme: str
    clients: List[Client]
    train_data: Dict[str, np.ndarray]
    test_data: Dict[str, np.ndarray]
    batch_size: int = 32
    local_steps: int = 5
    lr: float = 0.05
    seed: int = 0
    eval_batch: int = 512              # eval CHUNK size (full set is scored)
    #: partial participation: sample this many clients per round (0 = all).
    #: The population's Helios state persists across rounds; only the
    #: sampled cohort trains, and §IV.C pace/volume adaptation runs over it.
    participation: int = 0
    #: cohort sampler: "uniform", or "time_weighted" (p ∝ 1/cycle_time, so
    #: fast devices are drawn more often and the round critical path drops)
    sampler: str = "uniform"
    #: async event processes (federated.events): completion-delay jitter and
    #: per-event update loss.  None = the deterministic Table-I cost model.
    #: Both engines call them once per event in pop order, so a fixed seed
    #: still gives engine-identical trajectories.
    arrival: Optional[ArrivalProcess] = None
    dropout: Optional[DropoutProcess] = None
    #: max distinct compiled programs kept per engine (round shapes, bucket
    #: shapes); least-recently-used programs are evicted beyond this
    round_cache_cap: int = 8
    #: soft-training execution substrate: "reference" (plain jnp masked ops)
    #: or "pallas" (block-sparse masked-matmul + flash-attention kernels,
    #: kernels/ops.py — interpret mode on CPU, native on TPU).  Every engine
    #: (seq/batched/sharded/async) accepts both and produces the same
    #: trajectory at atol 1e-5 (tests/test_kernel_softtrain.py).
    kernels: str = "reference"
    #: kernel skip granularity.  0 (default) = follow HeliosConfig.
    #: mask_block (falling back to 128 when that is 0 too), so block-
    #: granular Eq. 2 selection and the kernels' skip blocks stay in sync
    #: from the ONE knob; set explicitly only to decouple them.
    mask_block: int = 0
    #: uplink compression — the comms/memory twin of ``kernels``, threaded
    #: through every engine the same way.  "none" keeps today's exact
    #: trajectories; the lossy modes compress each simulated
    #: client->server delta at the aggregation boundary with per-client
    #: error feedback (optim.compression, host-resident accumulators)
    #: masked by the Eq. 2 masks: "topk" (top-``comp_frac`` coords, fp16
    #: values), "quant" (dense int-``comp_bits``), "delta" (top-k +
    #: int-``comp_bits`` values).  quant/delta additionally switch the
    #: async snapshot ring to the matching lossy anchor store.
    compression: str = "none"
    comp_frac: float = 0.05
    comp_bits: int = 8
    #: async ring freshness window: anchors staler than this many
    #: aggregation steps decode from the int ring rows; fresher ones read
    #: a small rotating full-precision buffer (exact)
    comp_fresh: int = 8
    #: DGC-style compression warmup: the first ``comp_warmup`` SYNC rounds
    #: upload dense (bit-identical to ``compression="none"``) before the
    #: lossy codec kicks in — closes the documented topk/delta early-round
    #: convergence gap.  Counts global ``self.round``s; the async event
    #: loops have no round index and always compress.
    comp_warmup: int = 0
    #: telemetry recorder (repro.obs).  None builds a fresh one, armed
    #: only when ``REPRO_OBS=on``; pass one to arm explicitly or to share
    #: a sink across runs.  Every legacy engine counter
    #: (``uplink_updates``, ``events_processed``, ``agg_counter``, …) is
    #: a read-only property view onto it.
    recorder: Optional[OBS.Recorder] = None
    #: serve-while-you-train publish seam: when set, every
    #: ``publish_every``-th sync round writes the global params to this
    #: directory as an atomic checkpoint (repro.checkpoint: tmp write +
    #: fsync + os.replace) with ``{"round", "sim_time", "scheme"}``
    #: metadata, keep-``publish_keep`` GC'd.  A ``launch.serve.ServeLoop``
    #: polling the directory hot-swaps onto each publish; atomicity means
    #: it can never observe a partial snapshot.
    publish_dir: Optional[str] = None
    publish_every: int = 1
    publish_keep: int = 3

    def __post_init__(self):
        #: the resolved algorithm policy — every scheme decision in the
        #: engines reads this object (never the raw string again)
        self._scheme: Scheme = make_scheme(self.scheme)
        self.mask_block = self.mask_block or self.hcfg.mask_block or 128
        self.adapter = make_adapter(self.cfg, self.kernels, self.mask_block)
        self.api = self.adapter.api
        self.axes = self.adapter.axes
        self.global_params = init_params(jax.random.PRNGKey(self.seed),
                                         self.cfg)
        self.opt = make_optimizer("momentum", self.lr)
        self.rng = np.random.default_rng(self.seed)
        # participation draws live on their OWN stream: every engine
        # (sequential / batched / sharded) reconstructs the identical
        # schedule from the seed, and full-participation runs stay
        # draw-for-draw unchanged when sampling is off
        self.sample_rng = np.random.default_rng((self.seed, 0x5EED))
        self.cohort_log: List[List[int]] = []
        self.history: List[dict] = []
        self.round = 0
        if self.compression not in CP.MODES:
            raise ValueError(f"compression must be one of {CP.MODES}, "
                             f"got {self.compression!r}")
        if self.comp_fresh < 1:
            raise ValueError("comp_fresh must be >= 1 (the ring keeps at "
                             "least the newest anchor full-precision)")
        if self.comp_warmup < 0:
            raise ValueError("comp_warmup must be >= 0")
        if self.publish_every < 1:
            raise ValueError("publish_every must be >= 1")
        self._comp_total, self._comp_leaves = \
            CP.param_census(self.global_params)
        #: the unified accounting surface (repro.obs): uplink/downlink
        #: update counts are host-int recorder counters, ``uplink_coords``
        #: a DEVICE scalar accumulated eagerly (no host sync in the hot
        #: loops; converted once in :meth:`uplink_bytes`).
        #: ``uplink_dense_updates`` counts the warmup-round updates that
        #: bypassed the codec; ``uplink_extra_updates`` the scheme's dense
        #: side-channel (SCAFFOLD control deltas).
        self.rec = self.recorder if self.recorder is not None \
            else OBS.Recorder()
        self.rec.accum("uplink_coords", jnp.float32(0.0))
        if self.compression != "none":
            self._err_store = CP.HostErrorStore(self.global_params)
        self._init_helios()
        self._jit()
        self._scheme.init_run(self)
        if self.rec.armed:                 # manifest is an emission-side
            self.rec.manifest.update(self._obs_manifest())

    # ------------------------------------------------------------------
    def _init_helios(self):
        for c in self.clients:
            c.helios_state = ST.init_state(self.adapter.schema,
                                           volume=c.volume, seed=c.cid)

    def _jit(self):
        self._local_train = jax.jit(_make_local_train(
            self.adapter, self.opt, self._scheme.uses_control))
        self._eval_chunk = jax.jit(self.adapter.eval_chunk)
        if self.compression != "none":
            mode, frac, bits = self.compression, self.comp_frac, \
                self.comp_bits

            def compress_one(base, new_params, err, pmasks):
                delta = jax.tree.map(
                    lambda n, b: n.astype(jnp.float32)
                    - b.astype(jnp.float32), new_params, base)
                sent, new_err, coords = CP.compress_update(
                    delta, err, mode, frac, bits, pmasks)
                hat = jax.tree.map(
                    lambda b, s: (b.astype(jnp.float32) + s).astype(b.dtype),
                    base, sent)
                return hat, new_err, coords

            # the sequential engines' per-update codec (batched/sharded/
            # bucketed engines trace the same math inside their programs)
            self._compress_one = jax.jit(compress_one)

    # ------------------------------------------------------------------
    def _ring_mode(self) -> str:
        """Snapshot-ring anchor precision keyed off the uplink knob:
        quant/delta compress the ring the matching way; none/topk keep the
        exact fp32 store (top-k has no dense-anchor analogue)."""
        return self.compression \
            if self.compression in ("quant", "delta") else "fp32"

    def uplink_bytes(self) -> float:
        """Total simulated client->server wire bytes so far.

        Syncs ``uplink_coords`` once — call from benches/tests, never a
        hot loop.  ``none`` moves every param dense-f32 per update; the
        lossy formulas live in :func:`repro.optim.compression.uplink_bytes`.
        Warmup-round updates and scheme side-channels (SCAFFOLD control
        deltas) are billed dense.
        """
        dense = float(self.uplink_extra_updates) * self._comp_total * 4.0
        if self.compression == "none":
            return dense + float(self.uplink_updates) * self._comp_total * 4.0
        coords = self.rec.accum_value("uplink_coords")
        comp_updates = self.uplink_updates - self.uplink_dense_updates
        return (dense
                + float(self.uplink_dense_updates) * self._comp_total * 4.0
                + CP.uplink_bytes(self.compression, coords, self._comp_total,
                                  self._comp_leaves * comp_updates,
                                  self.comp_bits))

    def downlink_bytes(self) -> float:
        """Total simulated server->client broadcast bytes so far — the
        accounting twin of :meth:`uplink_bytes` (PR 7 modeled only the
        uplink).  Every participating update (sync cohort member,
        processed async event) pulls the dense fp32 global; downlink
        compression is not modeled, so this is pure host arithmetic."""
        return float(self.downlink_updates) * self._comp_total * 4.0

    # -- legacy counter views (the recorder is the single surface) -------
    @property
    def uplink_updates(self) -> int:
        return self.rec.count("uplink_updates")

    @property
    def uplink_dense_updates(self) -> int:
        return self.rec.count("uplink_dense_updates")

    @property
    def uplink_extra_updates(self) -> int:
        return self.rec.count("uplink_extra_updates")

    @property
    def uplink_coords(self):
        return self.rec.accum_raw("uplink_coords", jnp.float32(0.0))

    @property
    def downlink_updates(self) -> int:
        return self.rec.count("downlink_updates")

    @property
    def events_processed(self) -> int:
        return self.rec.count("events_processed")

    @property
    def events_dropped(self) -> int:
        return self.rec.count("events_dropped")

    @property
    def agg_counter(self) -> int:
        return self.rec.count("agg_counter")

    @property
    def snapshot_peak(self) -> int:
        return self.rec.count("snapshot_peak", 1)

    @property
    def snapshot_anchor_misses(self) -> int:
        return self.rec.count("snapshot_anchor_misses")

    # -- telemetry ------------------------------------------------------
    def _obs_manifest(self) -> dict:
        """Run-identifying manifest for the telemetry sinks: engine,
        scheme (with its full flag census), family, the kernel and
        compression knobs, population shape, seeds, and the git sha."""
        return {"engine": type(self).__name__,
                "scheme": self.scheme,
                "scheme_flags": self._scheme.manifest(),
                "family": self.cfg.family,
                "model": self.cfg.name,
                "kernels": self.kernels,
                "mask_block": self.mask_block,
                "compression": self.compression,
                "comp_frac": self.comp_frac,
                "comp_bits": self.comp_bits,
                "comp_warmup": self.comp_warmup,
                "clients": len(self.clients),
                "participation": self.participation,
                "sampler": self.sampler,
                "local_steps": self.local_steps,
                "batch_size": self.batch_size,
                "lr": self.lr,
                "seed": self.seed,
                "git_sha": OBS.git_sha()}

    def _obs_finish(self, seam: str) -> None:
        """End-of-run telemetry (armed only — a disarmed run does zero
        extra work and zero extra host transfers here): final byte
        gauges, the error-store census, and the contracts bridge
        (compile report + contract counters), so a flushed run log is
        self-contained."""
        if not self.rec.armed:
            return
        self.rec.gauge("uplink_mb", self.uplink_bytes() / 1e6)
        self.rec.gauge("downlink_mb", self.downlink_bytes() / 1e6)
        if self.compression != "none":
            self.rec.event("error_store", seam=seam,
                           **self._err_store.stats())
        CT.emit_obs(self, self.rec)

    def _comp_active(self) -> bool:
        """Whether THIS sync round's uplink goes through the lossy codec
        (False during the first ``comp_warmup`` rounds — those run the
        exact same program a ``compression="none"`` run compiles, so the
        warmup prefix is bit-identical to an uncompressed run)."""
        return self.compression != "none" and self.round >= self.comp_warmup

    def _get_cached_program(self, key, builder):
        """LRU of compiled programs; elastic churn (or per-draw cohort /
        bucket shapes) returning to a recently-seen key pays no recompile,
        and keys beyond ``round_cache_cap`` are evicted."""
        if not hasattr(self, "_round_cache"):
            self._round_cache = OrderedDict()
        if key in self._round_cache:
            self._round_cache.move_to_end(key)
        else:
            self._round_cache[key] = builder()
            while len(self._round_cache) > self.round_cache_cap:
                self._round_cache.popitem(last=False)
        return self._round_cache[key]

    # ------------------------------------------------------------------
    def _sample_batches(self, client: Client) -> dict:
        return self.adapter.sample_batch(self.rng, self.train_data,
                                         client.data_idx, self.local_steps,
                                         self.batch_size)

    def _client_masks(self, client: Client) -> dict:
        if self._scheme.soft_training and client.is_straggler:
            return client.helios_state["masks"]
        return ST.full_masks(self.adapter.schema)

    def _client_cycle(self, client: Client, base_params):
        """One local training cycle; returns (new_params, masks, ratio)."""
        sch = self._scheme
        soft = sch.soft_training and client.is_straggler
        hcfg = sch.effective_hcfg(self.hcfg)
        if soft:
            client.helios_state = ST.begin_cycle(client.helios_state, hcfg)
        masks = self._client_masks(client)
        batches = self._sample_batches(client)
        if sch.uses_control:
            corr = jax.tree.map(lambda cg, ci: cg - jnp.asarray(ci),
                                self._c_global,
                                self._ctrl_store.row(client.cid))
            new_params, loss = self._local_train(base_params, batches,
                                                 masks, corr)
            # option-II control update from the RAW trained params (before
            # any uplink codec): dc = (x - y)/(K*lr) - c_global
            inv = 1.0 / (self.local_steps * self.lr)
            dc = jax.tree.map(
                lambda b, y, cg: (b.astype(jnp.float32)
                                  - y.astype(jnp.float32)) * inv - cg,
                base_params, new_params, self._c_global)
            self._ctrl_store.set_row(
                client.cid,
                jax.tree.map(lambda ci, d: jnp.asarray(ci, jnp.float32) + d,
                             self._ctrl_store.row(client.cid), dc))
            self._dc_buf.append(dc)
        else:
            new_params, loss = self._local_train(base_params, batches, masks)
        if soft:
            if sch.use_delta_scores:
                scores = self.adapter.cycle_scores(new_params, base_params)
            else:                                          # random [12]
                scores = client.helios_state["scores"]
            client.helios_state = ST.end_cycle(client.helios_state, scores,
                                               hcfg)
        # device scalars on purpose: the hot loops never sync on these —
        # they are converted behind the eval gate (_record_round / history)
        ratio = MK.selected_fraction(masks)
        return new_params, masks, ratio, loss

    def _apply_control(self) -> None:
        """Fold buffered client control deltas into the server control —
        after the cohort in sync rounds (all clients corrected by the
        round-start c_global, the SCAFFOLD parallel semantics), after each
        event in the async fallback."""
        if not self._dc_buf:
            return
        n = float(len(self.clients))
        for dc in self._dc_buf:
            self._c_global = jax.tree.map(lambda c, d: c + d / n,
                                          self._c_global, dc)
        self._dc_buf = []

    def _aggregate(self, results):
        """results: list of (params, masks, ratio)."""
        params = [r[0] for r in results]
        ratios = [r[2] for r in results]
        mode = self._scheme.agg_mode(self.hcfg)
        if mode == "masked_mean":
            pmasks = [self.adapter.expand_masks(r[1], self.global_params)
                      for r in results]
            self.global_params = AG.aggregate_masked_mean(
                self.global_params, params, pmasks, ratios)
        else:
            self.global_params = AG.aggregate(mode, self.global_params,
                                              params, ratios=ratios)

    def evaluate(self) -> float:
        """Full-test-set metric in jitted chunks of ``eval_batch``.

        A weighted mean over chunks, so the reported number is never a
        fixed-subset estimate (the last ragged chunk pays one extra compile).
        """
        n = self.adapter.num_examples(self.test_data)
        total = weight = 0.0
        for lo in range(0, n, self.eval_batch):
            chunk = self.adapter.eval_slice(self.test_data, lo,
                                            min(lo + self.eval_batch, n))
            s, w = self._eval_chunk(self.global_params, chunk)
            # evaluate() IS the deliberate sync point (callers gate on
            # eval_every); the per-chunk sync is intended
            total += float(s)     # repro: noqa[R3]
            weight += float(w)    # repro: noqa[R3]
        return total / max(weight, 1e-9)

    # ------------------------------------------------------------------
    # shared per-round host protocol (the sync template method)
    # ------------------------------------------------------------------
    def _draw_cohort(self) -> List[int]:
        """This round's participant indices (sorted, duplicate-free).

        Full participation returns every client.  Sampling consumes ONE
        ``sample_rng`` draw per round, so for a fixed seed every engine
        reproduces the identical participant schedule.  ``time_weighted``
        weights clients by inverse simulated cycle time at their CURRENT
        volume — all engines evolve volumes with the same host arithmetic,
        so the weights (and draws) also agree bit-for-bit.
        """
        n = len(self.clients)
        k = self.participation
        if not k or k >= n:
            return list(range(n))
        if self.sampler == "uniform":
            p = None
        elif self.sampler == "time_weighted":
            # the weights ARE _round_times over the fleet — one expression,
            # one scheme hook (Scheme.effective_volume), so the sampler and
            # the round clock can never disagree on what a straggler costs
            # (the pre-seam code duplicated the volume conditional here and
            # relied on keeping the two copies mirrored by hand)
            t = np.asarray(self._round_times())
            w = 1.0 / np.maximum(t, 1e-9)
            p = w / w.sum()
        else:
            raise ValueError(f"unknown sampler {self.sampler!r}")
        idx = self.sample_rng.choice(n, size=k, replace=False, p=p)
        return sorted(int(i) for i in idx)

    def _round_times(self, clients: Optional[Sequence["Client"]] = None) \
            -> List[float]:
        """Simulated wall time per client for one round, billed at the
        scheme's effective volume (full-model schemes never see the
        soft-training volumes)."""
        return [cycle_time(c.profile, self._scheme.effective_volume(c))
                for c in (self.clients if clients is None else clients)]

    def _record_round(self, r: int, rounds: int, eval_every: int,
                      clock: float, losses, ratios):
        """History bookkeeping shared by all sync engines; eval_every=0
        disables evaluation/history entirely (pure-throughput benchmarks).
        Takes the raw per-client losses/ratios (device scalars or arrays)
        and converts to host floats HERE, behind the eval gate — the
        run_sync hot loop itself never forces a device->host sync."""
        if eval_every > 0 and (r % eval_every == 0 or r == rounds - 1):
            with self.rec.span("fl.evaluate", sim=clock, round=r):
                self.history.append({
                    "scheme": self.scheme, "cycle": r + 1, "time": clock,
                    "record_cadence": "round",
                    self.adapter.metric_name: self.evaluate(),
                    "loss": float(np.mean(np.asarray(losses))),
                    "ratios": [float(x) for x in np.asarray(ratios)],
                    "volumes": [c.volume for c in self.clients],
                    "downlink_mb": self.downlink_bytes() / 1e6})
            row = self.history[-1]
            self.rec.event("history", sim=row["time"],
                           **{k: v for k, v in row.items() if k != "time"})

    def run_sync(self, rounds: int, eval_every: int = 1) -> List[dict]:
        """The ONE sync host loop (every scheme with async_native=False).

        Template method: every engine runs this exact per-round protocol
        (draw cohort -> §IV.C pace -> simulated times -> scheme round_start
        -> engine-specific ``_train_cohort`` -> volume adaptation -> scheme
        round_end -> clock/record) and only overrides the hooks.  Each
        round trains only the drawn cohort (everyone under full
        participation); unsampled clients keep their Helios state
        untouched.  The pace is computed over the sampled cohort — at full
        participation it equals the whole-fleet pace, so sampling off
        reproduces the original trajectory exactly.
        """
        clock = 0.0
        for r in range(rounds):
            with self.rec.span("fl.round", sim=clock, round=r):
                clock = self._sync_round(r, rounds, eval_every, clock)
        self._finish_sync()
        if CT.enabled():
            # one compiled program per seam per shape signature, and every
            # surviving straggler mask still satisfies the Eq. 2 structure
            CT.check_compile_budget(self, tag="run_sync.compile")
            for masks in self._contract_state_masks():
                CT.check_mask_invariants(
                    masks, block=self.hcfg.mask_block, tag="run_sync.masks")
        self._obs_finish("run_sync")   # after the walls: counters complete
        return self.history

    def _sync_round(self, r: int, rounds: int, eval_every: int,
                    clock: float) -> float:
        """One round of :meth:`run_sync`; returns the advanced clock.  Its
        host phases are spans in the ring (``fl.sample``, ``fl.stack``,
        ``fl.dispatch``, ``fl.writeback`` inside the batched engines'
        ``_train_cohort``; ``fl.adapt``; ``fl.evaluate``; ``publish``);
        the cohort draw and the scheme's ``round_start`` are the
        enclosing ``fl.round``'s own time."""
        cohort = self._draw_cohort()
        self.cohort_log.append(cohort)
        cclients = [self.clients[i] for i in cohort]
        pace = _collab_pace(cclients)
        times = self._round_times(cclients)
        self.rec.inc("downlink_updates", len(cohort))   # global broadcast
        self._scheme.round_start(self)
        # contract: the round's device work never syncs to host —
        # losses/ratios stay device values until _record_round's gate
        with self.rec.maybe_profile(r), \
                CT.no_host_transfers("run_sync[" + self.scheme + "]"):
            losses, ratios = self._train_cohort(cohort, cclients)
        self.rec.inc("uplink_updates", len(cohort))
        if self.compression != "none" and not self._comp_active():
            self.rec.inc("uplink_dense_updates", len(cohort))  # warmup
        self.rec.inc("uplink_extra_updates",
                     len(cohort) * self._scheme.extra_dense_uplink)
        CT.assert_finite(self.global_params, tag="run_sync.global_params")
        with self.rec.span("fl.adapt", sim=clock, round=r):
            self._adapt_volumes(cohort, cclients, times, pace)
            self._scheme.round_end(self)
        dur = self._scheme.round_duration(times, cclients)
        clock += dur
        self.round += 1
        self.rec.event("round", sim=clock, round=r, cohort=len(cohort),
                       pace=pace, duration=dur)
        self.rec.event("volumes", sim=clock, round=r,
                       volumes=[self._scheme.effective_volume(c)
                                for c in cclients if c.is_straggler])
        if self.publish_dir and (r + 1) % self.publish_every == 0:
            self._publish_round(r, clock)
        self._record_round(r, rounds, eval_every, clock, losses, ratios)
        return clock

    # -- engine hooks ---------------------------------------------------
    def _train_cohort(self, cohort: List[int], cclients: List[Client]):
        """Train the drawn cohort against the current global params and
        aggregate; returns per-client (losses, ratios) in cohort order.
        The sequential reference: one re-dispatched ``_local_train`` per
        client, consuming ``self.rng`` in cohort order (the draw order
        every other engine replays)."""
        sch = self._scheme
        results = []
        for c in cclients:
            stale = sch.uses_stale_base and c.is_straggler
            base = self._stale_base if stale else self.global_params
            r = self._client_cycle(c, base)
            if stale:
                # delayed-gradient hybrid: virtualize the stale-base update
                # onto the CURRENT global with the staleness discount, so
                # it rides the normal aggregation (and the uplink codec
                # compresses p_virtual - global like any other delta)
                disc = self._stale_disc
                p = jax.tree.map(
                    lambda g, y, b: (g.astype(jnp.float32)
                                     + disc * (y.astype(jnp.float32)
                                               - b.astype(jnp.float32))
                                     ).astype(g.dtype),
                    self.global_params, r[0], base)
                r = (p,) + r[1:]
            results.append(r)
        if sch.uses_control:
            self._apply_control()
        if self._comp_active():
            results = self._compress_results(cclients, results)
        self._aggregate(results)
        return [x[3] for x in results], [x[2] for x in results]

    def _compress_results(self, cclients: List[Client], results):
        """Lossy uplink for the sequential reference: replace each raw
        new-params with the decoded compressed update (base + sent),
        folding the un-sent residual into the client's error accumulator.
        Eq. 2 masks gate the encoder, so frozen coordinates are never
        sent (their residual survives until rotation wakes them)."""
        base = self.global_params
        out = []
        for c, r in zip(cclients, results):
            pmasks = self.adapter.expand_masks(r[1], base)
            hat, new_err, coords = self._compress_one(
                base, r[0], self._err_store.row(c.cid), pmasks)
            self._err_store.set_row(c.cid, new_err)
            self.rec.accum("uplink_coords", coords)
            out.append((hat,) + r[1:])
        return out

    def _adapt_volumes(self, cohort: List[int], cclients: List[Client],
                       times: List[float], pace: float) -> None:
        """Volume adaptation toward the collaboration pace (§IV.C) — host
        arithmetic shared verbatim by every engine; only the state
        write-back (``_write_volumes``) is engine-specific."""
        if not (self._scheme.adapt_volume and self.hcfg.adapt_volume):
            return
        upd = [j for j, c in enumerate(cclients) if c.is_straggler]
        for j in upd:
            c = cclients[j]
            c.volume = VOL.adapt_volume(c.volume, times[j], pace,
                                        self.hcfg.adapt_gain,
                                        self.hcfg.min_volume)
        if upd:
            self._write_volumes(cohort, cclients, upd)

    def _write_volumes(self, cohort: List[int], cclients: List[Client],
                       upd: List[int]) -> None:
        for j in upd:
            cclients[j].helios_state = ST.set_volume(
                cclients[j].helios_state, cclients[j].volume)

    def _finish_sync(self) -> None:
        pass

    def _publish_round(self, r: int, clock: float) -> None:
        """Round-end publish seam (serve-while-you-train): snapshot the
        current global params atomically so a concurrently-polling
        ``ServeLoop`` can hot-swap onto it.  Shared verbatim by every
        engine that runs the ``run_sync`` template."""
        with self.rec.span("publish", sim=clock, round=r):
            CKPT.save(self.publish_dir, self.round, self.global_params,
                      keep=self.publish_keep,
                      metadata={"round": self.round, "sim_time": clock,
                                "scheme": self.scheme})
        self.rec.inc("published_snapshots")
        self.rec.event("publish", sim=clock, round=r, step=self.round)

    def _contract_state_masks(self):
        """Mask trees the post-run contract sweep validates (structure
        only: 0/1 and block-constant; the count check needs the
        selection-time volume and runs in soft_train.begin_cycle's
        contract instead).  Engines that keep state elsewhere override."""
        return [c.helios_state["masks"] for c in self.clients
                if c.is_straggler and isinstance(c.helios_state, dict)
                and "masks" in c.helios_state]

    # ------------------------------------------------------------------
    # async (event-driven) reference engine
    # ------------------------------------------------------------------
    def _next_delay(self, client: Client) -> float:
        """Delay until this client's next completion — the Table-I cost
        model, optionally perturbed by the pluggable arrival process."""
        base = cycle_time(client.profile, 1.0)
        return self.arrival.delay(client.cid, base) if self.arrival else base

    def _reset_async_processes(self) -> None:
        for p in (self.arrival, self.dropout):
            if p is not None:
                p.reset(self.seed)

    def run_async(self, capable_cycles: int, mix_weight: float = 0.5,
                  staleness_a: float = 0.5, eval_every: int = 1,
                  snapshot_cap: int = 64) -> List[dict]:
        """asyn / afo reference: one un-jitted client cycle per completion
        event, Python-dict snapshots.  :class:`AsyncFLRun` reproduces this
        trajectory with bucketed device execution."""
        clock = SimClock()
        self._reset_async_processes()
        snapshots = {0: self.global_params}
        # lossy-ring reference semantics: snapshots stay full precision in
        # the dict, but an anchor read past the freshness window decodes
        # through the SAME quantize->dequantize the bucketed ring's rows
        # pay at write time (bit-identical, deterministic)
        ring_mode = self._ring_mode()
        ring_ref = jax.tree.map(lambda x: x.astype(jnp.float32),
                                self.global_params) \
            if ring_mode == "delta" else None
        # bookkeeping exposed for tests/monitoring: the snapshot dict must
        # stay bounded by cap + len(clients) and never evict a live anchor
        self.rec.set("snapshot_peak", 1)
        self.rec.set("snapshot_anchor_misses", 0)
        self.rec.set("events_processed", 0)
        self.rec.set("events_dropped", 0)
        for c in self.clients:
            c.staleness_anchor = 0
            clock.schedule(self._next_delay(c), c.cid)
        done_fast = 0
        agg_counter = 0
        by_id = {c.cid: c for c in self.clients}
        while done_fast < capable_cycles and not clock.empty():
            cid = clock.pop()
            c = by_id[cid]
            if self.dropout is not None and self.dropout.drops(cid):
                self.rec.inc("events_dropped")
                self.rec.event("drop", sim=clock.now, cid=cid)
                clock.schedule(self._next_delay(c) * self.dropout.penalty,
                               cid)
                continue
            # anchors are never evicted (below), so this lookup cannot fall
            # back to the current global params and mislabel staleness
            base = snapshots[c.staleness_anchor]
            stale = agg_counter - c.staleness_anchor
            self.rec.event("completion", sim=clock.now, cid=cid, stale=stale)
            self.rec.observe("staleness", stale)
            CT.check_staleness([stale], a=staleness_a, tag="run_async[seq]")
            with CT.no_host_transfers("run_async[seq]"):
                if ring_mode != "fp32" and stale >= self.comp_fresh:
                    base = AG.lossy_roundtrip(base, ring_ref, self.comp_bits)
                new_params, masks_u, _, loss = self._client_cycle(c, base)
                if self.compression != "none":
                    pmasks = self.adapter.expand_masks(masks_u, base)
                    new_params, new_err, coords = self._compress_one(
                        base, new_params, self._err_store.row(c.cid), pmasks)
                    self._err_store.set_row(c.cid, new_err)
                    self.rec.accum("uplink_coords", coords)
                self.rec.inc("uplink_updates")
                self.rec.inc("uplink_extra_updates",
                             self._scheme.extra_dense_uplink)
                w = self._scheme.async_weight(mix_weight, stale, staleness_a)
                self.global_params = AG.mix(self.global_params, new_params, w)
                if self._scheme.uses_control:
                    self._apply_control()      # per event: async semantics
            agg_counter += 1
            snapshots[agg_counter] = self.global_params
            c.staleness_anchor = agg_counter
            if len(snapshots) > snapshot_cap:
                # evict oldest-first, but only snapshots no live client is
                # anchored to — a slow straggler keeps its base alive, so
                # the dict is bounded by snapshot_cap + len(clients)
                anchored = {cl.staleness_anchor for cl in self.clients}
                for k in sorted(snapshots):
                    if len(snapshots) <= snapshot_cap:
                        break
                    if k != agg_counter and k not in anchored:
                        del snapshots[k]
                # eviction is the only step that could drop an anchor, so
                # the invariant check stays off the no-eviction fast path
                self.rec.inc("snapshot_anchor_misses", sum(
                    cl.staleness_anchor not in snapshots
                    for cl in self.clients))
            self.rec.set_max("snapshot_peak", len(snapshots))
            clock.schedule(self._next_delay(c), cid)
            self.rec.inc("events_processed")
            self.rec.inc("downlink_updates")   # the event's snapshot pull
            self.rec.observe("queue_depth", len(clock))
            if not c.is_straggler:
                done_fast += 1
                if eval_every > 0 and done_fast % eval_every == 0:
                    self.history.append({
                        "scheme": self.scheme, "cycle": done_fast,
                        "time": clock.now,
                        "record_cadence": "event",
                        self.adapter.metric_name: self.evaluate(),
                        # behind the eval gate: evaluate() just synced
                        "loss": float(loss),  # repro: noqa[R3]
                        "staleness": stale,
                        "downlink_mb": self.downlink_bytes() / 1e6})
                    row = self.history[-1]
                    self.rec.event("history", sim=row["time"],
                                   **{k: v for k, v in row.items()
                                      if k != "time"})
        self.rec.set("agg_counter", agg_counter)
        self.rec.set("queue_peak", clock.peak_depth)
        CT.check_snapshot_bound(self.snapshot_peak,
                                self.snapshot_anchor_misses,
                                snapshot_cap, len(self.clients),
                                tag="run_async[seq].snapshots")
        self._obs_finish("run_async[seq]")
        return self.history

    # ------------------------------------------------------------------
    # elastic scalability (§VI.C)
    # ------------------------------------------------------------------
    def add_client(self, profile: DeviceProfile, data_idx: np.ndarray,
                   white_box: bool = True) -> Client:
        """New device joins mid-flight: identify -> assign volume -> admit."""
        cid = max((c.cid for c in self.clients), default=-1) + 1
        if white_box:
            times, stragglers = identify_resource_based(
                workload_gflop=100.0, memory_mb=200.0,
                devices=[c.profile for c in self.clients] + [profile])
            is_straggler = len(self.clients) in stragglers or \
                profile.speed_factor > 1.5
        else:
            sim = [cycle_time(c.profile, 1.0) for c in self.clients] + \
                [cycle_time(profile, 1.0)]
            times, stragglers = identify_time_based(
                lambda d: None, len(sim), simulated_times=sim)
            is_straggler = len(self.clients) in stragglers
        pace = _collab_pace(self.clients)
        vol = VOL.volume_from_profile(cycle_time(profile, 1.0), pace,
                                      self.hcfg.min_volume) \
            if is_straggler else 1.0
        c = Client(cid=cid, profile=profile, data_idx=data_idx, volume=vol,
                   is_straggler=is_straggler)
        c.helios_state = ST.init_state(self.adapter.schema, volume=vol,
                                       seed=cid)
        self.clients.append(c)
        return c

    def remove_client(self, cid: int) -> None:
        self.clients = [c for c in self.clients if c.cid != cid]


@dataclasses.dataclass
class AsyncFLRun(FLRun):
    """Bucketed event-driven engine for the async schemes (asyn / afo).

    The sequential ``run_async`` dispatches one un-jitted client cycle per
    completion event from a Python dict of full-model snapshots — host
    overhead O(events), which caps the population size the simulator can
    reach.  This engine keeps the event semantics bit-compatible but
    executes them in bulk:

    * the deterministic event core (:class:`federated.events.SimClock`)
      pops a BUCKET of near-simultaneous completions per step (with the
      default ``bucket_horizon=0.0`` a bucket is exactly one equal-time
      tie-group, which provably cannot reorder events vs. the sequential
      loop — a client's next completion is strictly later than its
      current one);
    * every client in the bucket trains from its own anchor snapshot, read
      as a traced gather out of a device-side stacked **snapshot ring
      buffer** (:class:`core.aggregation.SnapshotRing`) — anchors predate
      the bucket, so the whole bucket's local training runs under ONE
      ``jax.vmap``;
    * the per-event mixing θ ← (1-w)θ + w θ_c (staleness-discounted for
      afo) folds over the bucket in event order inside the same program
      (:func:`core.aggregation.mix_bucket_ring`), writing each post-mix
      global into the ring slot the completing client re-anchors to;
    * buckets are padded to the next power of two (padding replicates slot
      0's batch without consuming host RNG, mixes at weight 0, and writes
      to the ring's scratch row), so at most log2(max_bucket)+1 programs
      are ever compiled — one per bucket-shape signature.

    Batch draws, arrival/dropout process draws, snapshot anchoring, and
    mixing order all replay the sequential reference exactly: for a fixed
    seed the two engines produce the same GLOBAL-PARAM trajectory up to
    vmapped-reduction float error (tests/test_async_engine.py).  History
    is the one deliberate divergence: the sequential loop records at every
    eval_every-th capable completion (possibly mid-tie-group), while this
    engine records at most once per bucket, after the bucket's mixes.
    """

    #: bucket events within this much virtual time of the earliest pending
    #: one.  0.0 = exact tie-groups (sequential-equivalent); > 0 trades
    #: exactness for bigger buckets (the clock advances per bucket).
    bucket_horizon: float = 0.0
    #: cap on events per bucket (bounds the vmapped program's memory)
    max_bucket: int = 128

    #: what cohorts are sampled from (:meth:`_place_train_data`), and the
    #: mesh it was placed for
    _cohort_data = None
    _cohort_mesh = None

    def _place_train_data(self, mesh: Optional[Mesh]) -> None:
        """Keep the training set on the device (on every device of
        ``mesh``) when it fits there (:func:`fits_on_device`), so that each
        cohort's batches are gathered on the device from host-drawn indices;
        otherwise the batches are looked up in the host arrays, as the
        sequential engine does.  Decided once per mesh."""
        if self._cohort_data is not None and self._cohort_mesh == mesh:
            return
        self._cohort_mesh = mesh
        devices = jax.devices()[:1] if mesh is None else mesh.devices.flat
        nbytes = sum(np.asarray(v).nbytes for v in self.train_data.values())
        if fits_on_device(nbytes, device_bytes_limit(devices)):
            self._cohort_data = DeviceData(self.train_data, mesh)
            self.rec.gauge("resident_train_bytes", self._cohort_data.nbytes)
        else:
            self._cohort_data = self.train_data
            self.rec.gauge("resident_train_bytes", 0)

    def _cohort_batches(self, clients: Sequence[Client],
                        mesh: Optional[Mesh] = None, **kw):
        """The cohort's stacked batches through the adapter's sampler,
        drawn from ``self.rng`` in ``clients`` order, rows split over
        ``mesh`` where given; places the training set on the first call
        (:meth:`_place_train_data`) and counts the cohorts gathered on the
        device and those looked up on the host."""
        self._place_train_data(mesh)
        on_device = isinstance(self._cohort_data, DeviceData)
        self.rec.inc("sample_device_cohorts" if on_device
                     else "sample_host_cohorts")
        return self.adapter.sample_cohort(
            self.rng, self._cohort_data, [c.data_idx for c in clients],
            self.local_steps, self.batch_size, **kw)

    def _make_bucket_fn(self, bpad: int):
        adapter, opt = self.adapter, self.opt
        ones_masks = ST.full_masks(adapter.schema)
        local_train = _make_local_train(adapter, opt)
        discount = self._scheme.staleness_discount
        comp, frac, bits = self.compression, self.comp_frac, self.comp_bits
        ring_mode = self._ring_mode()

        if comp == "none":
            def bucket_fn(global_params, ring_params, base_slots,
                          write_slots, batches, stale, valid, mix_w,
                          stale_a):
                base = jax.tree.map(
                    lambda x: jnp.take(x, base_slots, axis=0), ring_params)
                trained, losses = jax.vmap(
                    lambda bp, b: local_train(bp, b, ones_masks))(base,
                                                                  batches)
                w = jnp.full((bpad,), 1.0, jnp.float32) * mix_w
                if discount:
                    w = w * AG.staleness_weights(stale, stale_a)
                w = w * valid
                new_global, new_ring = AG.mix_bucket_ring(
                    global_params, ring_params, write_slots, trained, w)
                return new_global, new_ring, losses

            return bucket_fn

        def bucket_fn(global_params, ring_state, ref, err, base_slots,
                      write_slots, fresh_read, fresh_write, is_fresh,
                      batches, stale, valid, mix_w, stale_a):
            """Compressed bucket: decode anchors (lossy ring), train,
            compress deltas with error feedback, mix the decoded updates
            and re-encode the snapshot rows — all one program."""
            if ring_mode == "fp32":                        # topk uplink
                ring_params, = ring_state
                base = jax.tree.map(
                    lambda x: jnp.take(x, base_slots, axis=0), ring_params)
            else:
                q, sc, fr = ring_state
                base = AG.ring_gather_lossy(q, sc, fr, ref, base_slots,
                                            fresh_read, is_fresh)
            trained, losses = jax.vmap(
                lambda bp, b: local_train(bp, b, ones_masks))(base, batches)
            delta = jax.tree.map(
                lambda t, b: t.astype(jnp.float32) - b.astype(jnp.float32),
                trained, base)
            sent, new_err, coords = jax.vmap(
                lambda d, e: CP.compress_update(d, e, comp, frac, bits))(
                    delta, err)
            hat = jax.tree.map(
                lambda b, s: (b.astype(jnp.float32) + s).astype(b.dtype),
                base, sent)
            w = jnp.full((bpad,), 1.0, jnp.float32) * mix_w
            if discount:
                w = w * AG.staleness_weights(stale, stale_a)
            w = w * valid
            coords_sum = jnp.sum(coords * valid)
            if ring_mode == "fp32":
                new_global, new_ring = AG.mix_bucket_ring(
                    global_params, ring_params, write_slots, hat, w)
                return (new_global, (new_ring,), losses, new_err,
                        coords_sum)
            new_global, q2, sc2, fr2 = AG.mix_bucket_ring_lossy(
                global_params, q, sc, fr, ref, write_slots, fresh_write,
                hat, w, bits)
            return new_global, (q2, sc2, fr2), losses, new_err, coords_sum

        return bucket_fn

    def _get_bucket_fn(self, bpad: int):
        """Bucket programs get their OWN cache, not the round-program LRU:
        pow2 padding bounds the key set at log2(max_bucket)+1, and sharing
        the LRU would let a sync round key evict bucket programs (and vice
        versa) into a silent recompile-per-revisit thrash."""
        if not hasattr(self, "_bucket_cache"):
            self._bucket_cache: Dict[int, object] = {}
        if bpad not in self._bucket_cache:
            # donate globals + ring: both are dead in the caller the moment
            # the call returns (immediately reassigned), and without
            # donation every bucket would copy the whole N+1-snapshot ring
            self._bucket_cache[bpad] = jax.jit(self._make_bucket_fn(bpad),
                                               donate_argnums=(0, 1))
        return self._bucket_cache[bpad]

    def bucket_programs(self) -> Dict[int, int]:
        """{padded bucket size: jit cache size} — the equivalence wall and
        the bench assert every value is 1 (no per-bucket retraces)."""
        return {bpad: fn._cache_size() for bpad, fn in
                getattr(self, "_bucket_cache", {}).items()}

    def run_async(self, capable_cycles: int, mix_weight: float = 0.5,
                  staleness_a: float = 0.5, eval_every: int = 1,
                  snapshot_cap: int = 64) -> List[dict]:
        if not self._scheme.async_native:
            # non-native schemes (soft-training mask evolution, control
            # variates, stale bases) need per-event state the bucket
            # program does not carry — only the sequential reference
            # implements that event-by-event; the bucket program trains
            # full models (the asyn/afo semantics)
            return super().run_async(capable_cycles, mix_weight,
                                     staleness_a, eval_every, snapshot_cap)
        clock = SimClock()
        self._reset_async_processes()
        n = len(self.clients)
        by_id = {c.cid: c for c in self.clients}
        ring = AG.SnapshotRing(self.global_params, snapshot_cap, n,
                               mode=self._ring_mode(), bits=self.comp_bits,
                               fresh_window=self.comp_fresh)
        lossy_ring = ring.mode != "fp32"
        for c in self.clients:
            c.staleness_anchor = 0
            ring.alloc.retain(0)
            clock.schedule(self._next_delay(c), c.cid)
        self.rec.set("agg_counter", 0)
        self.rec.set("events_processed", 0)
        self.rec.set("events_dropped", 0)
        self.bucket_sizes: List[int] = []
        done_fast = 0
        next_rec = eval_every if eval_every > 0 else 0
        while done_fast < capable_cycles and not clock.empty():
            evs = clock.pop_bucket(self.bucket_horizon, self.max_bucket)
            # dropout draws + capable-budget truncation, in event order —
            # the sequential loop stops mid-tie-group when the budget runs
            # out, so the bucket must cut at the same event and put the
            # unprocessed tail back on the heap untouched
            exec_evs: List[Event] = []
            drop_cids = set()
            budget = capable_cycles - done_fast
            cut = None
            for i, ev in enumerate(evs):
                if self.dropout is not None and self.dropout.drops(ev.cid):
                    drop_cids.add(ev.cid)
                    self.rec.event("drop", sim=ev.time, cid=ev.cid)
                    continue
                # the event stream mirrors the sequential reference: one
                # completion per executed event, emitted in pop order with
                # drops interleaved, staleness counted pre-mix
                self.rec.event("completion", sim=ev.time, cid=ev.cid,
                               stale=self.agg_counter + len(exec_evs)
                               - by_id[ev.cid].staleness_anchor)
                exec_evs.append(ev)
                if not by_id[ev.cid].is_straggler:
                    budget -= 1
                    if budget == 0:
                        cut = i + 1
                        break
            handled = evs if cut is None else evs[:cut]
            for ev in evs[len(handled):]:
                clock.schedule_at(ev.time, ev.cid)
            b = len(exec_evs)
            losses = stales = None
            if b:
                bpad = 1 << (b - 1).bit_length()
                # per-event batch draws in pop order — bit-identical rng
                # consumption to the sequential loop; padding replicates
                # slot 0 without touching the stream (PR 3's cohort seam)
                batches = self._cohort_batches(
                    [by_id[ev.cid] for ev in exec_evs], pad_to=bpad)
                agg0 = self.agg_counter
                base_slots, write_slots, stales = [], [], []
                fresh_read, fresh_write, is_fresh = [], [], []
                F = ring.fresh_window
                for i, ev in enumerate(exec_evs):
                    c = by_id[ev.cid]
                    base_slots.append(ring.alloc.slot_of(c.staleness_anchor))
                    stales.append(agg0 + i - c.staleness_anchor)
                    # freshness is decided per EVENT (same stale < window
                    # rule as the sequential reference); the anchor's fp row
                    # is still live because agg ids inside the window can't
                    # have been overwritten (one fresh write per agg)
                    fresh_read.append(c.staleness_anchor % F)
                    is_fresh.append(1.0 if stales[-1] < F else 0.0)
                    new_agg = agg0 + i + 1
                    ring.alloc.release(c.staleness_anchor)
                    write_slots.append(ring.alloc.alloc(new_agg))
                    ring.alloc.retain(new_agg)
                    c.staleness_anchor = new_agg
                    fresh_write.append(new_agg % F)
                self.rec.set("agg_counter", agg0 + b)
                for s in stales:
                    self.rec.observe("staleness", s)
                CT.check_staleness(stales, a=staleness_a,
                                   tag="run_async[bucket]")
                pad = bpad - b
                bucket_fn = self._get_bucket_fn(bpad)
                if self.compression == "none":
                    with CT.no_host_transfers("run_async[bucket]"):
                        self.global_params, ring.params, losses = bucket_fn(
                            self.global_params, ring.params,
                            jnp.asarray(base_slots + [0] * pad, jnp.int32),
                            jnp.asarray(write_slots + [ring.scratch] * pad,
                                        jnp.int32),
                            batches,
                            jnp.asarray(stales + [0] * pad, jnp.float32),
                            jnp.asarray([1.0] * b + [0.0] * pad,
                                        jnp.float32),
                            float(mix_weight), float(staleness_a))
                else:
                    cids = [ev.cid for ev in exec_evs]
                    err = self._err_store.gather(cids + [cids[0]] * pad)
                    ring_state = ((ring.q, ring.scales, ring.fresh_buf)
                                  if lossy_ring else (ring.params,))
                    ref = ring.ref if lossy_ring else None
                    with CT.no_host_transfers("run_async[bucket]"):
                        (self.global_params, ring_state, losses, new_err,
                         coords) = bucket_fn(
                            self.global_params, ring_state, ref, err,
                            jnp.asarray(base_slots + [0] * pad, jnp.int32),
                            jnp.asarray(write_slots + [ring.scratch] * pad,
                                        jnp.int32),
                            jnp.asarray(fresh_read + [0] * pad, jnp.int32),
                            # padding writes the fresh buffer's scratch row
                            jnp.asarray(fresh_write + [F] * pad, jnp.int32),
                            jnp.asarray(is_fresh + [1.0] * pad,
                                        jnp.float32),
                            batches,
                            jnp.asarray(stales + [0] * pad, jnp.float32),
                            jnp.asarray([1.0] * b + [0.0] * pad,
                                        jnp.float32),
                            float(mix_weight), float(staleness_a))
                        self.rec.accum("uplink_coords", coords)
                    if lossy_ring:
                        ring.q, ring.scales, ring.fresh_buf = ring_state
                    else:
                        ring.params, = ring_state
                    self._err_store.scatter(
                        cids, jax.tree.map(lambda x: x[:b], new_err))
                self.rec.inc("uplink_updates", b)
                self.rec.inc("events_processed", b)
                self.rec.inc("downlink_updates", b)  # per-event ring pulls
                self.bucket_sizes.append(b)
                self.rec.observe("bucket_size", b)
                self.rec.observe("queue_depth", len(clock))
                done_fast += sum(1 for ev in exec_evs
                                 if not by_id[ev.cid].is_straggler)
            # reschedule every handled event in event order (arrival-stream
            # parity with the sequential reference; each process owns its
            # rng, so drop draws above never perturb these)
            for ev in handled:
                delay = self._next_delay(by_id[ev.cid])
                if ev.cid in drop_cids:
                    delay *= self.dropout.penalty
                clock.schedule_at(ev.time + delay, ev.cid)
            self.rec.inc("events_dropped", len(drop_cids))
            if next_rec and b and done_fast >= next_rec:
                self.history.append({
                    "scheme": self.scheme, "cycle": done_fast,
                    "time": clock.now,
                    "record_cadence": "bucket",
                    self.adapter.metric_name: self.evaluate(),
                    # behind the eval gate: evaluate() just synced
                    "loss": float(np.mean(np.asarray(losses)[:b])),  # repro: noqa[R3]
                    "staleness": float(np.mean(stales)),
                    "bucket": b,
                    "downlink_mb": self.downlink_bytes() / 1e6})
                row = self.history[-1]
                self.rec.event("history", sim=row["time"],
                               **{k: v for k, v in row.items()
                                  if k != "time"})
                next_rec = (done_fast // eval_every + 1) * eval_every
        self.rec.set("snapshot_peak", ring.alloc.peak_live)
        self.rec.set("snapshot_anchor_misses", ring.alloc.anchor_misses)
        self.rec.set("queue_peak", clock.peak_depth)
        if CT.enabled():
            CT.check_ring(ring, len(self.clients),
                          tag="run_async[bucket].ring")
            CT.check_compile_budget(self, tag="run_async[bucket].compile")
        self._obs_finish("run_async[bucket]")
        return self.history


class BatchedFLRun(AsyncFLRun):
    """Batched sync engine: one jitted vmapped program per round.

    Per-client Helios state (masks, scores, skip_counts, volume, rng,
    cycle) is stacked along a leading client axis.  Clients are split into
    two COHORTS so every control decision inside the traced program is
    uniform:

      * soft-training stragglers — begin_cycle (batched PRNG split + Eq. 2
        selection) -> masked local training (lax.scan over steps) ->
        cycle_scores / end_cycle, all under one vmap;
      * capable clients — full-model local training under a second vmap.

    Both cohorts and the Eq. 10 / masked-mean aggregation trace into a
    SINGLE compiled round program, so host-loop dispatch overhead is O(1)
    per round instead of O(clients).  Host-side pieces run through the
    shared template-method protocol in the same order as the sequential
    reference — which keeps the engines trajectory-equivalent for a fixed
    seed (up to batched-reduction float error).

    The async schemes run on the inherited bucketed event engine
    (:class:`AsyncFLRun`) — no sequential fallback.
    """

    def __post_init__(self):
        super().__post_init__()
        self._build_batched()

    # ------------------------------------------------------------------
    def _get_round_fn(self, n_s: int, n_c: int):
        # the warmup phase is part of the program identity: warmup rounds
        # run the EXACT program a compression="none" run compiles (so the
        # prefix is bit-identical), steady rounds the codec program — at
        # most one extra cache entry, each still holding one program
        on = self._comp_active()
        return self._get_cached_program(
            (n_s, n_c, on), lambda: jax.jit(self._make_round_fn(n_s, n_c,
                                                                on)))

    def _build_batched(self):
        soft = self._scheme.soft_training
        self._s_idx = [i for i, c in enumerate(self.clients)
                       if soft and c.is_straggler]
        self._c_idx = [i for i, c in enumerate(self.clients)
                       if not (soft and c.is_straggler)]
        if self.participation:
            # sampled cohorts change membership per round: per-client
            # ``helios_state`` stays authoritative and each round stacks /
            # unstacks just its cohort (_train_cohort) — no
            # persistent whole-fleet stacked state to fall out of sync
            self._sstate = None
            return
        # stacked[unperm] restores original client order for aggregation
        self._unperm = jnp.asarray(
            np.argsort(np.asarray(self._s_idx + self._c_idx)), jnp.int32)
        self._sstate = ST.stack_states(
            [self.clients[i].helios_state for i in self._s_idx]) \
            if self._s_idx else None
        # unperm is a traced arg, so programs depend only on (n_s, n_c)
        self._round_fn = self._get_round_fn(len(self._s_idx),
                                            len(self._c_idx))

    def _make_round_fn(self, n_s: int, n_c: int, comp_on: bool = True):
        adapter, opt = self.adapter, self.opt
        scheme, hcfg = self._scheme, self.hcfg
        hcfg_eff = scheme.effective_hcfg(hcfg)
        agg_mode = scheme.agg_mode(hcfg)
        ones_masks = ST.full_masks(adapter.schema)
        local_train = _make_local_train(adapter, opt, scheme.uses_control)
        comp = self.compression if comp_on else "none"
        frac, bits = self.comp_frac, self.comp_bits
        inv = 1.0 / (self.local_steps * self.lr)

        def round_fn(global_params, sstate, s_batch, c_batch, unperm,
                     *extras):
            # scheme extras ride positionally, in flag order (the host
            # _round_extras builds the mirror-image tuple)
            extras = list(extras)
            if scheme.uses_control:
                c_global, c_rows = extras.pop(0), extras.pop(0)
            if scheme.uses_stale_base:
                stale_base = extras.pop(0)
                stale_flags, discs = extras.pop(0), extras.pop(0)
            err = extras.pop(0) if comp != "none" else None

            def cat(parts):
                if len(parts) == 1:
                    return jax.tree.map(
                        lambda x: jnp.take(x, unperm, axis=0), parts[0])
                return jax.tree.map(
                    lambda *xs: jnp.take(jnp.concatenate(xs), unperm,
                                         axis=0), *parts)

            parts_p, parts_r, parts_l, parts_m = [], [], [], []
            new_sstate = sstate
            if n_s:
                def one_straggler(st, batches):
                    st = ST.begin_cycle(st, hcfg_eff)
                    masks = st["masks"]
                    p, loss = local_train(global_params, batches, masks)
                    if scheme.use_delta_scores:
                        scores = adapter.cycle_scores(p, global_params)
                    else:                                  # random [12]
                        scores = st["scores"]
                    st = ST.end_cycle(st, scores, hcfg_eff)
                    return (p, st, MK.selected_fraction(masks), loss, masks)

                # named scopes tag the device ops with the round's parts
                # (a trace's ``tf_op`` paths; bench/program_trace.py)
                with jax.named_scope("fl_straggler_train"):
                    p, new_sstate, r, l, m = jax.vmap(one_straggler)(
                        sstate, s_batch)
                parts_p.append(p), parts_r.append(r), parts_l.append(l)
                parts_m.append(m)
            if n_c:
                with jax.named_scope("fl_capable_train"):
                    if scheme.uses_control:
                        corr = jax.tree.map(lambda cg, cr: cg - cr,
                                            c_global, c_rows)

                        def one_capable(batches, co):
                            return local_train(global_params, batches,
                                               ones_masks, co)

                        p, l = jax.vmap(one_capable)(c_batch, corr)
                    elif scheme.uses_stale_base:
                        def one_capable(batches, flag, disc):
                            base = jax.tree.map(
                                lambda s, g: jnp.where(flag > 0,
                                                       s.astype(g.dtype), g),
                                stale_base, global_params)
                            p, loss = local_train(base, batches, ones_masks)
                            # virtualize onto the current global (capable rows:
                            # base == global, disc == 1 => exactly p)
                            p = jax.tree.map(
                                lambda g, y, b: (g.astype(jnp.float32) + disc
                                                 * (y.astype(jnp.float32)
                                                    - b.astype(jnp.float32))
                                                 ).astype(g.dtype),
                                global_params, p, base)
                            return p, loss

                        p, l = jax.vmap(one_capable)(c_batch, stale_flags,
                                                     discs)
                    else:
                        def one_capable(batches):
                            return local_train(global_params, batches,
                                               ones_masks)

                        p, l = jax.vmap(one_capable)(c_batch)
                parts_p.append(p)
                parts_r.append(jnp.ones((n_c,), jnp.float32))
                parts_l.append(l)
                parts_m.append(jax.tree.map(
                    lambda v: jnp.ones((n_c,) + v.shape, jnp.float32),
                    ones_masks))
            with jax.named_scope("fl_aggregate"):
                stacked = cat(parts_p)
                ratios = cat(parts_r)
                losses = cat(parts_l)
                ctrl_out = ()
                if scheme.uses_control:
                    # option-II control update from the RAW trained rows,
                    # before any codec touches them
                    dc = jax.tree.map(
                        lambda g, t, cg: (g.astype(jnp.float32)
                                          - t.astype(jnp.float32)) * inv - cg,
                        global_params, stacked, c_global)
                    new_c_rows = jax.tree.map(lambda rr, d: rr + d, c_rows, dc)
                    dc_sum = jax.tree.map(lambda d: jnp.sum(d, axis=0), dc)
                    ctrl_out = (new_c_rows, dc_sum)
                if comp == "none":
                    pmasks = adapter.expand_masks_batch(cat(parts_m),
                                                        global_params) \
                        if agg_mode == "masked_mean" else None
                    new_global = AG.aggregate_stacked(agg_mode, global_params,
                                                      stacked, ratios, pmasks)
                    return (new_global, new_sstate, ratios, losses) + ctrl_out
                # compressed uplink: every stacked update goes through the
                # codec + error feedback, masked so Eq. 2-frozen coordinates
                # are never encoded (capable rows carry ones masks)
                pm = adapter.expand_masks_batch(cat(parts_m), global_params)
                delta = jax.tree.map(
                    lambda t, g: t.astype(jnp.float32) - g.astype(jnp.float32),
                    stacked, global_params)
                sent, new_err, coords = jax.vmap(
                    lambda d, e, m: CP.compress_update(d, e, comp, frac, bits,
                                                       m))(delta, err, pm)
                stacked = jax.tree.map(
                    lambda g, s: (g.astype(jnp.float32) + s).astype(g.dtype),
                    global_params, sent)
                pmasks = pm if agg_mode == "masked_mean" else None
                new_global = AG.aggregate_stacked(agg_mode, global_params,
                                                  stacked, ratios, pmasks)
                return (new_global, new_sstate, ratios, losses, new_err,
                        jnp.sum(coords)) + ctrl_out

        return round_fn

    # -- template hooks -------------------------------------------------
    def _round_extras(self, row_clients: List[Client]):
        """Scheme-specific traced inputs, in the order the round program
        pops them (mirrors _make_round_fn).  Rows follow the program's
        stacked row order — the full-model schemes that use extras have no
        soft cohort, so that is exactly ``row_clients`` order."""
        extras = ()
        if self._scheme.uses_control:
            extras += (self._c_global, self._ctrl_store.gather(
                [c.cid for c in row_clients]))
        if self._scheme.uses_stale_base:
            flags = jnp.asarray([1.0 if c.is_straggler else 0.0
                                 for c in row_clients], jnp.float32)
            discs = jnp.asarray([self._stale_disc if c.is_straggler else 1.0
                                 for c in row_clients], jnp.float32)
            extras += (self._stale_base, flags, discs)
        return extras

    def _apply_round_outs(self, row_clients: List[Client], outs) -> None:
        """Write back the round program's trailing scheme outputs
        (SCAFFOLD: per-client control rows + the server control fold)."""
        if self._scheme.uses_control:
            new_c_rows, dc_sum = outs
            self._ctrl_store.scatter([c.cid for c in row_clients],
                                     new_c_rows)
            n = float(len(self.clients))
            self._c_global = jax.tree.map(lambda c, d: c + d / n,
                                          self._c_global, dc_sum)

    def _train_cohort(self, cohort: List[int], cclients: List[Client]):
        """Stack the cohort, run the (n_s, n_c)-shaped round program from
        the LRU cache, and write its outputs back.

        Full participation keeps the stragglers' Helios state stacked
        across rounds (``self._sstate``).  Under partial participation
        per-client ``helios_state`` is the source of truth between rounds
        (unsampled clients' state is literally untouched): the cohort's
        straggler rows are stacked and unstacked back.  Batch draws consume
        ``self.rng`` in cohort order — the same order as the sequential
        engine's loop — so trajectories stay replay-equivalent.
        """
        comp = self._comp_active()
        if self.participation:
            soft = self._scheme.soft_training
            s_pos = [j for j, c in enumerate(cclients)
                     if soft and c.is_straggler]
            c_pos = [j for j, c in enumerate(cclients)
                     if not (soft and c.is_straggler)]
        else:
            s_pos, c_pos = self._s_idx, self._c_idx
        with self.rec.span("fl.sample"):
            s_batch, c_batch = self._cohort_batches(cclients,
                                                    groups=(s_pos, c_pos))
        with self.rec.span("fl.stack"):
            if self.participation:
                unperm = jnp.asarray(np.argsort(np.asarray(s_pos + c_pos)),
                                     jnp.int32)
                sstate = ST.stack_states([cclients[j].helios_state
                                          for j in s_pos]) if s_pos else None
            else:
                unperm, sstate = self._unperm, self._sstate
            args = (self.global_params, sstate, s_batch, c_batch,
                    unperm) + self._round_extras(cclients)
            # stacked rows are in cohort order (cat() un-permutes), so the
            # error rows gather/scatter in that same order
            cids = [c.cid for c in cclients]
            if comp:
                args += (self._err_store.gather(cids),)
            round_fn = self._get_round_fn(len(s_pos), len(c_pos))
        with self.rec.span("fl.dispatch"):
            outs = round_fn(*args)
        with self.rec.span("fl.writeback"):
            self.global_params, sstate, ratios, losses = outs[:4]
            if comp:
                new_err, coords = outs[4:6]
                self.rec.accum("uplink_coords", coords)
                self._err_store.scatter(cids, new_err)
            self._apply_round_outs(cclients, outs[6 if comp else 4:])
            if not self.participation:
                self._sstate = sstate
            elif s_pos:
                for j, st in zip(s_pos,
                                 ST.unstack_states(sstate, len(s_pos))):
                    cclients[j].helios_state = st
        # device arrays on purpose — _record_round converts behind the gate
        return losses, ratios

    def _write_volumes(self, cohort: List[int], cclients: List[Client],
                       upd: List[int]) -> None:
        if self.participation:
            super()._write_volumes(cohort, cclients, upd)
        elif self._s_idx:
            self._sstate = ST.set_volumes(
                self._sstate, [self.clients[i].volume for i in self._s_idx])

    def _finish_sync(self) -> None:
        # keep per-client helios_state fresh so callers that snapshot
        # clients (checkpointing, inspection) never see round-0 state
        if not self.participation:
            self.sync_client_states()

    # ------------------------------------------------------------------
    def run_async(self, *args, **kwargs) -> List[dict]:
        if self._scheme.async_native:
            return super().run_async(*args, **kwargs)      # bucketed engine
        # non-native schemes delegate to the sequential event loop (via
        # the AsyncFLRun guard), which mutates per-client helios_state:
        # materialize it from the stacked/population state, run, restack
        self.sync_client_states()
        hist = super().run_async(*args, **kwargs)
        self._build_batched()
        return hist

    def sync_client_states(self) -> None:
        """Write the stacked cohort state back into per-client
        ``helios_state`` (for checkpointing / inspection / elastic ops)."""
        if self._s_idx and self._sstate is not None:
            for i, st in zip(self._s_idx,
                             ST.unstack_states(self._sstate,
                                               len(self._s_idx))):
                self.clients[i].helios_state = st

    def add_client(self, profile: DeviceProfile, data_idx: np.ndarray,
                   white_box: bool = True) -> Client:
        self.sync_client_states()
        c = super().add_client(profile, data_idx, white_box)
        self._build_batched()                 # cohort shapes changed: re-jit
        return c

    def remove_client(self, cid: int) -> None:
        self.sync_client_states()
        super().remove_client(cid)
        self._build_batched()


@dataclasses.dataclass
class ShardedFLRun(BatchedFLRun):
    """Client-sharded round engine: the batched program, shard_mapped over a
    1-D ``("clients",)`` device mesh (launch/mesh.make_client_mesh).

    Population scale comes from three ingredients on top of
    :class:`BatchedFLRun`:

    * **Persistent population state** — every client's Helios state lives as
      one row of a stacked pytree (``core.soft_train.init_population``, built
      without materializing N per-client dicts).  Each round gathers the
      sampled cohort's rows, runs them, and scatters them back; unsampled
      rows are bit-untouched.
    * **One shape-stable round program** — the cohort is padded to
      ``ceil(K / devices) * devices`` slots (padding replicates the first
      client's batch, gets zero aggregation weight, and never consumes host
      RNG), and soft-training vs. capable clients are selected by a traced
      per-slot flag instead of cohort splitting.  One compiled program
      serves every draw: no recompiles across sampled cohorts.
    * **Client-parallel execution** — inside shard_map each device vmaps
      over its block of cohort rows; Eq. 10 / masked-mean aggregation is a
      local weighted partial sum followed by a single cross-device psum over
      the ``clients`` axis.

    Same seed => same trajectory as FLRun/BatchedFLRun up to float
    reduction-order error (the equivalence wall in
    tests/test_sharded_engine.py pins all three engines together).
    """

    #: optional explicit device mesh with a ``clients`` axis; by default a
    #: 1-D mesh over (at most cohort-size) visible devices is built lazily
    mesh: Optional[Mesh] = None

    # ------------------------------------------------------------------
    def _init_helios(self):
        # per-client dicts stay unmaterialized: the population state is
        # built stacked in _build_batched (sync_client_states writes rows
        # back on demand for checkpointing / elastic churn / inspection)
        pass

    def _build_batched(self):
        # _draw_cohort never returns more than the population, so clamp the
        # slot count too — otherwise participation > N pads every round
        # with zero-weight training slots
        k = min(self.participation, len(self.clients)) or len(self.clients)
        self._mesh = self.mesh if self.mesh is not None \
            else make_client_mesh(k)
        d = self._mesh.devices.size
        self._kpad = -(-k // d) * d
        # place the globals mesh-replicated up front: round 1 then sees the
        # same input sharding the round program outputs, so the compile
        # cache holds exactly ONE program from the first call on
        self.global_params = jax.device_put(
            self.global_params,
            jax.sharding.NamedSharding(self._mesh, P()))
        # the population state lives HOST-SIDE (numpy leaves): rounds gather
        # K rows to device and scatter them back in place, so N never
        # round-trips and the jit input signature is draw-invariant
        if all(c.helios_state is None for c in self.clients):
            self._pop_state = ST.host_states(ST.init_population(
                self.adapter.schema, [c.volume for c in self.clients],
                [c.cid for c in self.clients]))
        else:
            # elastic path: sync_client_states materialized fresh dicts
            # before the client list changed — restack them
            self._pop_state = ST.host_states(ST.stack_states(
                [c.helios_state for c in self.clients]))
        # warm the cache; the attribute stays for monitoring
        # (benchmarks read run._round_fn._cache_size())
        self._round_fn = self._get_sharded_fn()

    def _get_sharded_fn(self):
        # same warmup-phase cache split as _get_round_fn: one program per
        # (kpad, codec-on/off) signature
        on = self._comp_active()
        return self._get_cached_program(
            ("sharded", self._kpad, on),
            lambda: self._make_sharded_round_fn(self._kpad, on))

    def sync_client_states(self) -> None:
        """Materialize per-client ``helios_state`` views from the population
        rows (checkpointing / inspection / elastic ops)."""
        for i, c in enumerate(self.clients):
            c.helios_state = self.client_state(i)

    def client_state(self, i: int) -> dict:
        """Row ``i`` (client-list position) of the population state, as an
        immutable device snapshot (host rows are mutated in place)."""
        return jax.tree.map(lambda x: jnp.asarray(x[i]), self._pop_state)

    # ------------------------------------------------------------------
    def _make_sharded_round_fn(self, kpad: int, comp_on: bool = True):
        adapter, opt = self.adapter, self.opt
        scheme, hcfg = self._scheme, self.hcfg
        hcfg_eff = scheme.effective_hcfg(hcfg)
        agg_mode = scheme.agg_mode(hcfg)
        ones_masks = ST.full_masks(adapter.schema)
        local_train = _make_local_train(adapter, opt, scheme.uses_control)
        comp = self.compression if comp_on else "none"
        frac, bits = self.comp_frac, self.comp_bits
        inv = 1.0 / (self.local_steps * self.lr)

        def round_body(global_params, cstate, batches, is_soft, valid,
                       *extras):
            extras = list(extras)
            if scheme.uses_control:
                c_global, c_rows = extras.pop(0), extras.pop(0)
                corr = jax.tree.map(lambda cg, cr: cg - cr, c_global,
                                    c_rows)
            if scheme.uses_stale_base:
                stale_base = extras.pop(0)
                stale_flags, discs = extras.pop(0), extras.pop(0)
            err = extras.pop(0) if comp != "none" else None

            # block-local views: leading axis = kpad / n_devices rows
            def one_client(st, b, soft_flag, *row):
                st_b = ST.begin_cycle(st, hcfg_eff)
                masks = jax.tree.map(
                    lambda m, o: jnp.where(soft_flag > 0, m, o),
                    st_b["masks"], ones_masks)
                if scheme.uses_control:
                    co, = row
                    p, loss = local_train(global_params, b, masks, co)
                elif scheme.uses_stale_base:
                    flag, disc = row
                    base = jax.tree.map(
                        lambda s, g: jnp.where(flag > 0, s.astype(g.dtype),
                                               g),
                        stale_base, global_params)
                    p, loss = local_train(base, b, masks)
                    p = jax.tree.map(
                        lambda g, y, bb: (g.astype(jnp.float32) + disc
                                          * (y.astype(jnp.float32)
                                             - bb.astype(jnp.float32))
                                          ).astype(g.dtype),
                        global_params, p, base)
                else:
                    p, loss = local_train(global_params, b, masks)
                if scheme.use_delta_scores:
                    scores = adapter.cycle_scores(p, global_params)
                else:                                      # random [12] / syn
                    scores = st_b["scores"]
                st_e = ST.end_cycle(st_b, scores, hcfg_eff)
                # capable (and padding) slots keep their state bit-identical:
                # the discarded begin/end cycle never leaks back
                new_st = jax.tree.map(
                    lambda a, o: jnp.where(soft_flag > 0, a, o), st_e, st)
                ratio = jnp.where(soft_flag > 0,
                                  MK.selected_fraction(st_b["masks"]), 1.0)
                return p, new_st, ratio, loss, masks

            row_extra = ()
            if scheme.uses_control:
                row_extra = (corr,)
            elif scheme.uses_stale_base:
                row_extra = (stale_flags, discs)
            with jax.named_scope("fl_local_train"):
                p, new_state, ratios, losses, masks = jax.vmap(one_client)(
                    cstate, batches, is_soft, *row_extra)
            with jax.named_scope("fl_aggregate"):
                ctrl_out = ()
                if scheme.uses_control:
                    # option-II control update from the RAW trained rows;
                    # padding rows are masked out of the server fold by valid
                    dc = jax.tree.map(
                        lambda g, t, cg: (g.astype(jnp.float32)
                                          - t.astype(jnp.float32)) * inv - cg,
                        global_params, p, c_global)
                    new_c_rows = jax.tree.map(lambda rr, d: rr + d, c_rows, dc)
                    dc_sum = jax.tree.map(
                        lambda d: jax.lax.psum(
                            jnp.sum(d * valid.reshape((-1,) + (1,)
                                                      * (d.ndim - 1)), axis=0),
                            "clients"), dc)
                    ctrl_out = (new_c_rows, dc_sum)
                pm = adapter.expand_masks_batch(masks, global_params) \
                    if (comp != "none" or agg_mode == "masked_mean") else None
                if comp != "none":
                    # codec runs shard-local on each device's cohort rows;
                    # only the coordinate count crosses devices (one psum)
                    delta = jax.tree.map(
                        lambda t, g: t.astype(jnp.float32)
                        - g.astype(jnp.float32), p, global_params)
                    sent, new_err, coords = jax.vmap(
                        lambda d, e, m: CP.compress_update(d, e, comp, frac,
                                                           bits, m))(
                            delta, err, pm)
                    p = jax.tree.map(
                        lambda g, s: (g.astype(jnp.float32)
                                      + s).astype(g.dtype),
                        global_params, sent)
                    coords = jax.lax.psum(jnp.sum(coords * valid),
                                          "clients")
                base = ratios if agg_mode != "uniform" \
                    else jnp.ones_like(ratios)
                w = base * valid
                a = w / jnp.maximum(jax.lax.psum(jnp.sum(w), "clients"), 1e-9)
                if agg_mode == "masked_mean":
                    pmasks = pm
                    num = jax.tree.map(
                        lambda m, t: jnp.sum(
                            a.reshape((-1,) + (1,) * (t.ndim - 1)) * m
                            * t.astype(jnp.float32), axis=0), pmasks, p)
                    den = jax.tree.map(
                        lambda m: jnp.sum(
                            a.reshape((-1,) + (1,) * (m.ndim - 1)) * m,
                            axis=0),
                        pmasks)
                    num, den = jax.lax.psum((num, den), "clients")
                    new_g = jax.tree.map(
                        lambda g, nu, de: jnp.where(
                            de > 0, nu / jnp.maximum(de, 1e-9),
                            g.astype(jnp.float32)).astype(g.dtype),
                        global_params, num, den)
                else:
                    part = jax.tree.map(
                        lambda t: jnp.tensordot(a, t.astype(jnp.float32),
                                                axes=1), p)
                    part = jax.lax.psum(part, "clients")
                    new_g = jax.tree.map(lambda g, t: t.astype(g.dtype),
                                         global_params, part)
                if comp != "none":
                    return (new_g, new_state, ratios, losses, new_err,
                            coords) + ctrl_out
                return (new_g, new_state, ratios, losses) + ctrl_out

        # check_vma=False: remat checkpoint_name (transformer stacks) has no
        # replication rule on current JAX; the psum above still leaves
        # new_g replicated in practice
        in_specs = (P(), P("clients"), P("clients"), P("clients"),
                    P("clients"))
        out_specs = (P(), P("clients"), P("clients"), P("clients"))
        if scheme.uses_control:
            in_specs += (P(), P("clients"))                # c_global, rows
        if scheme.uses_stale_base:
            in_specs += (P(), P("clients"), P("clients"))  # base/flags/disc
        if comp != "none":
            in_specs += (P("clients"),)                    # err rows
            out_specs += (P("clients"), P())               # new_err, coords
        if scheme.uses_control:
            out_specs += (P("clients"), P())               # new rows, dc_sum
        sharded = jax.shard_map(
            round_body, mesh=self._mesh,
            in_specs=in_specs, out_specs=out_specs, check_vma=False)
        return jax.jit(sharded)

    # -- template hooks -------------------------------------------------
    def _round_extras(self, row_clients: List[Client]):
        """Sharded extras are PADDED to the program's kpad slots: padding
        replicates the first client's control row (its dc contribution is
        masked out by ``valid`` in-program) and trains from the fresh
        global at discount 1.  Dense trees are pinned mesh-replicated
        every round (idempotent device_put, same reason as the globals in
        _build_batched): after round 1 they are built FROM mesh-sharded
        round outputs, and letting the input sharding drift would retrace
        the round program against its compile budget."""
        rep = jax.sharding.NamedSharding(self._mesh, P())
        pad = self._kpad - len(row_clients)
        extras = ()
        if self._scheme.uses_control:
            cids = [c.cid for c in row_clients]
            extras += (jax.device_put(self._c_global, rep),
                       self._ctrl_store.gather(cids + [cids[0]] * pad))
        if self._scheme.uses_stale_base:
            flags = jnp.asarray(
                [1.0 if c.is_straggler else 0.0 for c in row_clients]
                + [0.0] * pad, jnp.float32)
            discs = jnp.asarray(
                [self._stale_disc if c.is_straggler else 1.0
                 for c in row_clients] + [1.0] * pad, jnp.float32)
            extras += (jax.device_put(self._stale_base, rep), flags, discs)
        return extras

    def _apply_round_outs(self, row_clients: List[Client], outs) -> None:
        if self._scheme.uses_control:
            new_c_rows, dc_sum = outs
            k = len(row_clients)
            self._ctrl_store.scatter(
                [c.cid for c in row_clients],
                jax.tree.map(lambda x: x[:k], new_c_rows))
            n = float(len(self.clients))
            self._c_global = jax.tree.map(lambda c, d: c + d / n,
                                          self._c_global, dc_sum)

    def _train_cohort(self, cohort: List[int], cclients: List[Client]):
        soft = self._scheme.soft_training
        comp = self._comp_active()
        k, kpad = len(cohort), self._kpad
        with self.rec.span("fl.sample"):
            batches = self._cohort_batches(cclients, self._mesh,
                                           pad_to=kpad)
        with self.rec.span("fl.stack"):
            idx = np.asarray(cohort + [cohort[0]] * (kpad - k))
            is_soft = jnp.asarray(
                [1.0 if (soft and c.is_straggler) else 0.0
                 for c in cclients] + [0.0] * (kpad - k), jnp.float32)
            valid = jnp.asarray([1.0] * k + [0.0] * (kpad - k), jnp.float32)
            args = (self.global_params, ST.gather_states_host(
                self._pop_state, idx), batches, is_soft, valid) \
                + self._round_extras(cclients)
            if comp:
                args += (self._err_store.gather(
                    [self.clients[i].cid for i in idx]),)
            round_fn = self._get_sharded_fn()
        with self.rec.span("fl.dispatch"):
            outs = round_fn(*args)
        # the host rows' write-back waits for the round program to end
        with self.rec.span("fl.writeback"):
            self.global_params, new_cstate, ratios, losses = outs[:4]
            if comp:
                new_err, coords = outs[4:6]
                self.rec.accum("uplink_coords", coords)
                self._err_store.scatter(
                    [self.clients[i].cid for i in cohort],
                    jax.tree.map(lambda x: x[:k], new_err))
            self._apply_round_outs(cclients, outs[6 if comp else 4:])
            ST.scatter_states_host(
                self._pop_state, cohort,
                jax.tree.map(lambda x: x[:k], new_cstate))
        # device slices on purpose — _record_round converts behind the gate
        return losses[:k], ratios[:k]

    def _write_volumes(self, cohort: List[int], cclients: List[Client],
                       upd: List[int]) -> None:
        self._pop_state["volume"][np.asarray([cohort[j] for j in upd])] = \
            np.asarray([cclients[j].volume for j in upd], np.float32)

    def _finish_sync(self) -> None:
        pass                # population rows ARE the authoritative state

    def _contract_state_masks(self):
        # straggler rows of the host-resident population state, checked
        # stacked (check_mask_invariants accepts leading client axes)
        s_idx = [i for i, c in enumerate(self.clients) if c.is_straggler]
        pop = getattr(self, "_pop_state", None)
        if not s_idx or not isinstance(pop, dict) or "masks" not in pop:
            return []
        idx = np.asarray(s_idx)
        return [{k: v[idx] for k, v in pop["masks"].items()}]


def setup_clients(profiles: Sequence[DeviceProfile],
                  parts: Sequence[np.ndarray],
                  hcfg: HeliosConfig,
                  identification: str = "resource") -> List[Client]:
    """Straggler identification (§IV.B) + volume targets (§IV.C)."""
    n = len(profiles)
    sim_times = [cycle_time(p, 1.0) for p in profiles]
    if identification == "resource":
        _, stragglers = identify_resource_based(
            workload_gflop=100.0, memory_mb=200.0, devices=list(profiles))
    else:
        _, stragglers = identify_time_based(lambda d: None, n,
                                            simulated_times=sim_times)
    pace = _median_pace([t for i, t in enumerate(sim_times)
                         if i not in stragglers])
    clients = []
    for i, p in enumerate(profiles):
        is_s = i in stragglers
        vol = VOL.volume_from_profile(sim_times[i], pace, hcfg.min_volume) \
            if is_s else 1.0
        clients.append(Client(cid=i, profile=p, data_idx=parts[i],
                              volume=vol, is_straggler=is_s))
    return clients
