"""Family adapters: the seam between model families and the FL round engines.

The round engines (federated.runtime) are family-blind: everything that
varies by model family — batch sampling and shapes (images+labels vs token
streams), the eval metric (accuracy vs cross-entropy), per-unit cycle-score
computation, and parameter-space mask expansion for masked-mean aggregation —
lives behind a :class:`FamilyAdapter`.  To federate a new family, implement
the five family hooks below and register it in :func:`make_adapter`; the
sequential and batched engines, elastic scaling, checkpointing, and the
schemes/baselines all come for free.

A family must provide:

* a ``ModelAPI`` (models.api.build) with a ``loss_fn(params, batch, cfg, rt,
  masks)`` and a ``mask_schema`` of maskable units;
* train/test data as a dict of aligned arrays whose keys match the model's
  batch dict (e.g. ``{"images", "labels"}`` or ``{"tokens"}``), indexed
  along axis 0 by example;
* an eval chunk reducer returning ``(metric_sum, weight)`` so the engines
  can evaluate the full test set in jitted chunks;
* per-unit contribution scores for a parameter delta (Eq. 1);
* unit-mask -> parameter-space mask expansion (masked-mean aggregation).

Both concrete adapters are vmap-safe: every hook that runs inside the
batched engine's round program (loss, scores, mask expansion) contains no
Python branching on traced values.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import contribution as C
from repro.core import masking as MK
from repro.models import build, default_runtime, logical_axes
from repro.models.cnn import cnn_logits
from repro.obs import recorder as OBS

#: model families whose batch is a plain token stream {"tokens": (B, S)}
TOKEN_FAMILIES = ("dense", "moe", "ssm", "hybrid")


def fits_on_device(nbytes: int, bytes_limit: Optional[int]) -> bool:
    """The resident-data rule: a training set of ``nbytes`` stays on a
    device when it takes at most a quarter of the device's memory
    (``bytes_limit``).  A backend that reports no limit (the CPU) counts
    as fitting."""
    return bytes_limit is None or nbytes <= bytes_limit // 4


def device_bytes_limit(devices) -> Optional[int]:
    """The smallest ``memory_stats()["bytes_limit"]`` among ``devices``, or
    None where the backend reports none."""
    limits = [s["bytes_limit"] for s in (d.memory_stats() for d in devices)
              if s and "bytes_limit" in s]
    return min(limits) if limits else None


class DeviceData:
    """A training set resident on the device: every leaf whole on the
    default device, or on each device of ``mesh``, with its host dtype.

    :meth:`gather` turns host-drawn example indices into stacked batches
    with one jitted gather program, so a round moves only the indices from
    the host, and the gather reads and writes at the device's memory speed.
    """

    def __init__(self, data: Dict[str, np.ndarray],
                 mesh: Optional[Mesh] = None):
        self.mesh = mesh
        where = NamedSharding(mesh, P()) if mesh is not None else None
        self.arrays = {k: jax.device_put(v, where) for k, v in data.items()}
        #: bytes of the copy on each device
        self.nbytes = sum(int(v.nbytes) for v in self.arrays.values())
        # this copy's own program cache: one program per index shapes
        self._gather = jax.jit(lambda arrays, takes: tuple(
            {k: v[t] for k, v in arrays.items()} for t in takes))

    def gather(self, parts: Sequence[np.ndarray]) -> tuple:
        """Stacked batches for each array of example indices in ``parts``
        (rows, steps, batch), in one transfer of the indices and one call
        of the gather program.  On a mesh the rows are split over its
        ``clients`` axis, so each device gathers its own rows from its own
        copy and nothing moves between devices."""
        rows = None if self.mesh is None else NamedSharding(self.mesh,
                                                            P("clients"))
        idx = jax.device_put(tuple(p.astype(np.int32) for p in parts), rows)
        return self._gather(self.arrays, idx)


class FamilyAdapter:
    """Base adapter: generic example-indexed data handling + family hooks."""

    #: history/metric key ("acc" higher-is-better, "ce" lower-is-better)
    metric_name: str = "metric"
    #: True when larger metric values are better (accuracy-style)
    higher_is_better: bool = True

    def __init__(self, cfg: ModelConfig, kernels: str = "reference",
                 mask_block: int = 128):
        self.cfg = cfg
        self.api = build(cfg)
        self.axes = logical_axes(cfg)
        self.schema = self.api.mask_schema
        #: execution substrate for the soft-training loss: "reference"
        #: (plain jnp) or "pallas" (block-sparse masked matmuls + flash
        #: attention, kernels/ops.py); ``mask_block`` is the skip
        #: granularity the kernels use (match HeliosConfig.mask_block)
        self.kernels = kernels
        self.mask_block = mask_block

    # -- data ----------------------------------------------------------
    def num_examples(self, data: Dict[str, np.ndarray]) -> int:
        return len(next(iter(data.values())))

    def draw_indices(self, rng: np.random.Generator, idx,
                     local_steps: int, batch_size: int) -> np.ndarray:
        """One client's (local_steps, batch_size) example indices, drawn
        from its index set ``idx`` with ONE host-RNG call (every engine
        replays the sequential engine's draw order).

        ``idx`` may be any array-like — in particular a lazy partition view
        (data.federated.LazyParts), which only materializes indices for the
        clients actually sampled into a round's cohort.
        """
        idx = np.asarray(idx)
        return rng.choice(idx, size=(local_steps, batch_size),
                          replace=len(idx) < local_steps * batch_size)

    def sample_batch(self, rng: np.random.Generator,
                     data: Dict[str, np.ndarray], idx: np.ndarray,
                     local_steps: int, batch_size: int) -> dict:
        """Draw a (local_steps, batch_size)-leading batch dict from one
        client's example indices (:meth:`draw_indices`), looked up in the
        host arrays."""
        take = self.draw_indices(rng, idx, local_steps, batch_size)
        return {k: jnp.asarray(v[take]) for k, v in data.items()}

    def sample_cohort(self, rng: np.random.Generator, data, idx_seq,
                      local_steps: int, batch_size: int, pad_to: int = 0,
                      groups: Optional[Sequence[Sequence[int]]] = None):
        """Per-client batches drawn in cohort order, stacked along a leading
        client axis.

        Padding slots (up to ``pad_to``: shard-divisible cohort shapes for
        the sharded engine, power-of-two event buckets for the async
        engine) replicate the first client's draw WITHOUT consuming the
        host RNG, so the padded engines stay draw-for-draw equivalent to
        the sequential references; engines give padding slots zero
        aggregation/mixing weight.  ``groups`` (lists of cohort positions)
        returns one stacked batch dict per group instead, None for an empty
        group.

        Only the indices are drawn and stacked on the host.  ``data`` is
        the host arrays, where they are looked up with numpy, or a
        :class:`DeviceData` copy resident on the device, where one gather
        program looks them up: both give the same arrays for the same draws.
        """
        takes = [self.draw_indices(rng, idx, local_steps, batch_size)
                 for idx in idx_seq]
        takes = np.stack(takes + [takes[0]] * (pad_to - len(takes)))
        parts = [takes] if groups is None else \
            [takes[list(g)] for g in groups if g]
        with OBS.span("fl.stack"):
            if isinstance(data, DeviceData):
                out = iter(data.gather(parts))
            else:
                out = iter([{k: jnp.asarray(v[t]) for k, v in data.items()}
                            for t in parts])
        if groups is None:
            return next(out)
        return tuple(next(out) if g else None for g in groups)

    def eval_slice(self, data: Dict[str, np.ndarray], lo: int,
                   hi: int) -> dict:
        return {k: jnp.asarray(v[lo:hi]) for k, v in data.items()}

    # -- family hooks --------------------------------------------------
    def loss_fn(self, params, batch, masks):
        """Masked training loss — traced inside the round program."""
        raise NotImplementedError

    def eval_chunk(self, params, batch):
        """(metric_sum, weight) over one test chunk — jitted by the engine."""
        raise NotImplementedError

    def cycle_scores(self, params_new, params_old):
        """Eq. 1 per-unit contribution scores of a cycle's parameter delta."""
        raise NotImplementedError

    def expand_masks(self, unit_masks, params_tree):
        """Unit masks -> params-shaped 0/1 tree (masked-mean aggregation)."""
        raise NotImplementedError

    def expand_masks_batch(self, unit_masks, params_tree):
        """``expand_masks`` over a stacked cohort (leading client axis).

        Works for any family whose ``expand_masks`` is vmap-safe, so new
        adapters get the batched aggregation path for free.
        """
        return jax.vmap(lambda um: self.expand_masks(um, params_tree))(
            unit_masks)


class CNNAdapter(FamilyAdapter):
    """Paper testbed: image classification, prefix-keyed mask schema."""

    metric_name = "acc"
    higher_is_better = True

    def loss_fn(self, params, batch, masks):
        rt = {"kernels": self.kernels, "mask_block": self.mask_block}
        return self.api.loss_fn(params, batch, self.cfg, rt, masks)

    def eval_chunk(self, params, batch):
        logits = cnn_logits(params, batch["images"], self.cfg)
        correct = jnp.sum(jnp.argmax(logits, -1) == batch["labels"])
        n = batch["labels"].shape[0]
        return correct.astype(jnp.float32), jnp.asarray(n, jnp.float32)

    def cycle_scores(self, params_new, params_old):
        return C.cnn_unit_scores(C.delta(params_new, params_old), self.schema)

    def expand_masks(self, unit_masks, params_tree):
        return MK.cnn_expand_masks(unit_masks, params_tree)


class TokenLMAdapter(FamilyAdapter):
    """Token-stream LMs (dense / moe / ssm / hybrid): axis-driven scores,
    cross-entropy eval, generic logical-axes mask expansion."""

    metric_name = "ce"
    higher_is_better = False

    def __init__(self, cfg: ModelConfig, kernels: str = "reference",
                 mask_block: int = 128):
        super().__init__(cfg, kernels, mask_block)
        self.rt = default_runtime(cfg)
        self.rt["kernels"] = kernels
        self.rt["mask_block"] = mask_block
        # eval always runs the reference substrate (matching CNNAdapter):
        # there are no masks to skip, so the kernels buy nothing — and on
        # CPU the interpret-mode flash kernel would slow every full-test-set
        # pass for free
        self.eval_rt = default_runtime(cfg)

    def loss_fn(self, params, batch, masks):
        return self.api.loss_fn(params, batch, self.cfg, self.rt, masks)

    def eval_chunk(self, params, batch):
        ce = self.api.loss_fn(params, batch, self.cfg, self.eval_rt, None)
        n = batch["tokens"].shape[0]
        return ce * n, jnp.asarray(n, jnp.float32)

    def cycle_scores(self, params_new, params_old):
        return C.unit_scores(C.delta(params_new, params_old), self.axes,
                             self.schema)

    def expand_masks(self, unit_masks, params_tree):
        return MK.expand_masks(self.axes, unit_masks, params_tree)


def make_adapter(cfg: ModelConfig, kernels: str = "reference",
                 mask_block: int = 128) -> FamilyAdapter:
    """Family dispatch for the FL engines.

    ``kernels="pallas"`` makes the adapter's loss run on the Pallas
    soft-training kernels (kernels/ops.py) — same trajectories as
    ``"reference"`` at atol 1e-5 (tests/test_kernel_softtrain.py).
    """
    if cfg.family == "cnn":
        return CNNAdapter(cfg, kernels, mask_block)
    if cfg.family in TOKEN_FAMILIES:
        return TokenLMAdapter(cfg, kernels, mask_block)
    supported = ("cnn",) + TOKEN_FAMILIES
    raise NotImplementedError(
        f"no FamilyAdapter for family {cfg.family!r} (supported families: "
        f"{supported}): encdec/vlm need extra input streams (enc_embeds / "
        "image_embeds) — subclass FamilyAdapter with a sample_batch that "
        "supplies them and register it here")
