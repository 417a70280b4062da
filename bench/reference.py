"""Plain reference of the synchronous Helios round (paper Sec. IV-VI).

Given a cell's inputs (``world.World``) and nothing that the engine made, it
replays the first rounds of the cell one client at a time:

* the cohort: every client, or ``participation`` clients drawn uniformly
  without replacement from ``default_rng((seed, 0x5EED))``, sorted;
* each client's batches: ``local_steps x batch`` examples drawn from its
  own by ``default_rng(seed)``, in cohort order, every key of the batch
  dict indexed alike;
* each straggler's unit masks by Eq. 2 at its volume P: top-``p_s`` by the
  previous cycle's contribution, a random rest, and units skipped for more
  than ``1 + 1/P`` cycles forced in; unit types at least four mask blocks
  wide are selected by whole blocks of block-mean scores;
* local training: ``local_steps`` of SGD with momentum from zero, on the
  configuration's loss (``bench/models/<model>.py``), masks multiplying
  the units' activations;
* Eq. 1 scores (the model's ``unit_scores``: the summed absolute change of
  every weight that carries the unit's axis) and skip counters;
* Eq. 10 aggregation: the mean of the client models weighted by each
  client's selected fraction of units;
* Sec. IV.C volume adaptation toward the median capable time.

Masks, scores and skip counters are ``(rows, n)`` per unit type, one row
per layer that the type spans (``mask_units``); Eq. 2 selects row by row.
Volumes start at pace / straggler time (Table I speed factors).  The random
streams are JAX's threefry keys and NumPy's PCG64 from the seeds named
above: the same seed gives the same cohorts, batches and masks as the
engine is documented to draw.

``dtype`` and ``precision`` set how it computes: float32 at ``highest`` is
the reference; bfloat16 is the control.  ``batch_keep`` and ``agg_keep``
plant faults for the checks of the comparison (half of each batch; the
aggregation over the first chip's share of the cohort only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import models
from bench.compare import host_leaves


def _row_select(u, forced, k_total, k_top, key):
    """Eq. 2 on one row of ``n`` units: forced, then top-k, then random."""
    n = u.shape[0]
    u = u + jax.random.uniform(key, (n,), minval=0.0, maxval=1e-6)
    thresh = jnp.sort(u)[jnp.clip(n - k_top, 0, n - 1)]
    is_top = jnp.where(k_top > 0, u >= thresh, False)
    rand = jax.random.uniform(jax.random.fold_in(key, 1), (n,))
    prio = forced.astype(jnp.float32) * 4.0 \
        + is_top.astype(jnp.float32) * 2.0 + rand
    pthresh = jnp.sort(prio)[jnp.clip(n - k_total, 0, n - 1)]
    return (prio >= pthresh).astype(jnp.float32)


def select(scores, forced, volume, p_s, key, block=0):
    """Eq. 2 over unit types ``{name: (rows, n)}``, row by row;
    block-granular for unit types at least ``4 * block`` wide."""
    if block:
        pooled = [k for k, u in scores.items() if u.shape[-1] >= 4 * block]
        if not pooled:
            return select(scores, forced, volume, p_s, key)

        def pool(u, how):
            rows, n = u.shape
            nb = -(-n // block)
            g = jnp.pad(u, ((0, 0), (0, nb * block - n))).reshape(
                rows, nb, block)
            if how == "max":
                return g.max(-1)
            cnt = jnp.minimum(block, n - jnp.arange(nb) * block)
            return g.sum(-1) / cnt[None, :]

        bm = select({k: pool(scores[k], "mean") for k in pooled},
                    {k: pool(forced[k].astype(jnp.float32), "max")
                     .astype(bool) for k in pooled},
                    volume, p_s, jax.random.fold_in(key, 0xB10C))
        unit = select({k: u for k, u in scores.items() if k not in pooled},
                      {k: f for k, f in forced.items() if k not in pooled},
                      volume, p_s, jax.random.fold_in(key, 0x0A11))
        return {k: jnp.repeat(bm[k], block, axis=-1)[..., :u.shape[-1]]
                if k in pooled else unit[k] for k, u in scores.items()}
    out = {}
    for i, (k, u) in enumerate(sorted(scores.items())):
        rows, n = u.shape
        k_total = jnp.clip(jnp.round(volume * n).astype(jnp.int32), 1, n)
        k_top = jnp.round(p_s * k_total).astype(jnp.int32)
        out[k] = jax.vmap(_row_select, in_axes=(0, 0, None, None, 0))(
            u, forced[k], k_total, k_top,
            jax.random.split(jax.random.fold_in(key, i), rows))
    return out


class Reference:
    """The plain federated round of one cell, one client at a time."""

    def __init__(self, world, dtype=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST, batch_keep=1.0,
                 agg_keep=1.0):
        from bench.world import seeds
        self.w, self.dtype = world, dtype
        tr, cfg = world.traffic, world.cfg
        if tr["scheme"] != "helios" or tr["aggregation"] != "alpha_weighted":
            raise ValueError("the reference follows Helios with Eq. 10")
        self.model = models.load(cfg["model"])
        self.units = self.model.mask_units(cfg)
        self.agg_keep = agg_keep
        engine_seed = seeds(world.seed)["engine"]
        self.rng = np.random.default_rng(engine_seed)
        self.sample_rng = np.random.default_rng((engine_seed, 0x5EED))
        self.volume = {}
        for cid, (strag, speed) in enumerate(world.fleet):
            if strag:
                self.volume[cid] = 1.0 if speed <= 1.0 else float(
                    np.clip(1.0 / speed, tr["min_volume"], 1.0))
        self.state = {}                     # cid -> Helios state
        self.params = jax.tree.map(lambda x: jnp.asarray(x, dtype),
                                   world.weights)
        self._train = jax.jit(self._make_train(precision, batch_keep))
        self._begin = jax.jit(self._begin_cycle)
        self._end = jax.jit(self._end_cycle)
        self._agg = jax.jit(self._aggregate)

    # -- traced pieces --------------------------------------------------
    def _make_train(self, precision, batch_keep):
        cfg, tr, model = self.w.cfg, self.w.traffic, self.model
        lr, beta = tr["lr"], tr["momentum"]
        keep = int(round(tr["batch"] * batch_keep))

        def loss_fn(p, batch, masks):
            return model.loss(p, {k: v[:keep] for k, v in batch.items()},
                              cfg, masks, precision)

        def train(params, batches, masks):
            def step(carry, batch):
                p, m = carry
                loss, g = jax.value_and_grad(loss_fn)(p, batch, masks)
                m = jax.tree.map(lambda mm, gg: beta * mm + gg, m, g)
                p = jax.tree.map(lambda pp, mm: pp - lr * mm, p, m)
                return (p, m), loss

            zeros = jax.tree.map(jnp.zeros_like, params)
            (p, _), losses = jax.lax.scan(step, (params, zeros), batches)
            return p, losses.mean()

        return train

    def _begin_cycle(self, st):
        tr = self.w.traffic
        rng, sub = jax.random.split(st["rng"])
        thresh = 1.0 + 1.0 / jnp.maximum(st["volume"], 1e-3)
        forced = {k: v.astype(jnp.float32) >= thresh
                  for k, v in st["skip"].items()}
        masks = select(st["scores"], forced, st["volume"], tr["p_s"], sub,
                       block=tr["mask_block"])
        return {**st, "masks": masks, "rng": rng}

    def _end_cycle(self, st, new, old):
        scores = self.model.unit_scores(new, old, self.w.cfg)
        skip = {k: jnp.where(st["masks"][k] > 0, 0, v + 1)
                for k, v in st["skip"].items()}
        return {**st, "scores": scores, "skip": skip}

    def _aggregate(self, stacked, ratios):
        a = ratios / jnp.sum(ratios)
        return jax.tree.map(
            lambda t: jnp.tensordot(a, t.astype(jnp.float32),
                                    axes=1).astype(self.dtype), stacked)

    # -- one round -----------------------------------------------------
    def _new_state(self, cid):
        ones = {k: jnp.ones(shape) for k, shape in self.units.items()}
        return {"masks": ones,
                "scores": jax.tree.map(jnp.zeros_like, ones),
                "skip": {k: jnp.zeros(shape, jnp.int32)
                         for k, shape in self.units.items()},
                "volume": jnp.float32(self.volume[cid]),
                "rng": jax.random.PRNGKey(cid)}

    def _cohort(self):
        n, k = self.w.n_clients, self.w.traffic["participation"]
        if not k or k >= n:
            return list(range(n))
        return sorted(int(i) for i in
                      self.sample_rng.choice(n, size=k, replace=False))

    def round(self):
        """One round; returns (mean loss, per-client ratios in cohort order,
        {straggler cid: its masks this round})."""
        tr, data, fleet = self.w.traffic, self.w.train, self.w.fleet
        cohort = self._cohort()
        shape = (tr["local_steps"], tr["batch"])
        rows = [self.rng.choice(self.w.parts[c], size=shape,
                                replace=len(self.w.parts[c]) < np.prod(shape))
                for c in cohort]
        # Sec. IV.C: simulated times at the round's volumes, and the pace
        times = [fleet[c][1] * (max(self.volume[c], 1e-3) if fleet[c][0]
                                else 1.0) for c in cohort]
        capable = [fleet[c][1] for c in cohort if not fleet[c][0]]
        pace = float(np.median(capable)) if capable else 1.0
        total = np.float32(self.w.units)
        ones = {k: jnp.ones(shape) for k, shape in self.units.items()}
        news, losses, ratios, masks = [], [], [], {}
        for cid, take in zip(cohort, rows):
            m = ones
            if fleet[cid][0]:
                st = self._begin(self.state.get(cid) or self._new_state(cid))
                masks[cid] = {k: np.asarray(v)
                              for k, v in st["masks"].items()}
                m = st["masks"]
            p, loss = self._train(
                self.params, {k: jnp.asarray(v[take])
                              for k, v in data.items()}, m)
            if fleet[cid][0]:
                self.state[cid] = self._end(st, p, self.params)
            alive = sum(float(jnp.sum(v)) for v in m.values())
            ratios.append(float(np.float32(alive) / total))
            news.append(p)
            losses.append(float(loss))
        keep = max(1, int(round(len(cohort) * self.agg_keep)))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *news[:keep])
        self.params = self._agg(stacked, jnp.asarray(ratios[:keep],
                                                     jnp.float32))
        for j, cid in enumerate(cohort):
            if fleet[cid][0]:
                self.volume[cid] = float(np.clip(
                    self.volume[cid] * (pace / times[j]) ** tr["adapt_gain"],
                    tr["min_volume"], 1.0))
                self.state[cid] = {**self.state[cid],
                                   "volume": jnp.float32(self.volume[cid])}
        return float(np.mean(losses)), ratios, masks

    def host_params(self):
        return host_leaves(self.params)
