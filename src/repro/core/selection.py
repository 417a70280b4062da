"""Neuron selection (paper Eq. 2) + rotation regulation (Section VI.A).

Per layer, per unit type, with volume fraction P and contribution scores U:

  selected = TopK(U) ∪ Rand(rest) ∪ Forced(C_s over threshold)
  |TopK| = P_s * P * n      (primary convergence guarantee, Prop. 2)
  |Rand| = (1-P_s) * P * n  (rotation -> model integrity)

Counts are TRACED (thresholding a sorted array) so the adaptive volume
controller can change P without recompiling.  Forced units (skipped for
C_s > threshold cycles, Section VI.A) preempt the random draw — "pull the
long-term skipped neurons back to training timely".
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import contracts as CT


def _select_masks_pre(scores, forced, volume, p_s, key, block=0):
    """Eq. 2 precondition: (L, n)-shaped score/forced rows, a scalar
    volume, and p_s in [0, 1].  Shape-level only, so it runs under
    jit/vmap tracing too (shapes are always concrete)."""
    for k, u in scores.items():
        if getattr(u, "ndim", None) != 2:
            raise CT.ContractError(
                f"select_masks: scores[{k!r}] must be (L, n), got "
                f"shape {getattr(u, 'shape', None)}")
        f = forced.get(k)
        if f is not None and f.shape != u.shape:
            raise CT.ContractError(
                f"select_masks: forced[{k!r}] shape {f.shape} != "
                f"scores shape {u.shape}")
    if getattr(volume, "shape", ()) not in ((), (1,)):
        raise CT.ContractError(
            f"select_masks: volume must be scalar, got shape "
            f"{volume.shape}")
    if not 0.0 <= float(p_s) <= 1.0:
        raise CT.ContractError(f"select_masks: p_s={p_s} outside [0, 1]")


def _row_select(u: jax.Array, forced: jax.Array, k_total: jax.Array,
                k_top: jax.Array, key: jax.Array) -> jax.Array:
    """One layer row.  u: (n,) scores; forced: (n,) bool; returns (n,) 0/1."""
    n = u.shape[0]
    noise = jax.random.uniform(key, (n,), minval=0.0, maxval=1e-6)
    u = u + noise                                         # random tie-break

    # top-k by threshold on the sorted scores (k is traced)
    su = jnp.sort(u)
    idx_top = jnp.clip(n - k_top, 0, n - 1)
    thresh = su[idx_top]
    is_top = jnp.where(k_top > 0, u >= thresh, False)

    # priority: forced >> top >> random
    rand = jax.random.uniform(jax.random.fold_in(key, 1), (n,))
    prio = forced.astype(jnp.float32) * 4.0 + is_top.astype(jnp.float32) * 2.0 + rand
    sp = jnp.sort(prio)
    idx_tot = jnp.clip(n - k_total, 0, n - 1)
    pthresh = sp[idx_tot]
    mask = (prio >= pthresh).astype(jnp.float32)
    return mask


def _pool_blocks(u: jax.Array, block: int, reduce: str) -> jax.Array:
    """(L, n) unit values -> (L, ceil(n/block)) per-block values.

    ``mean`` averages over the REAL entries of the ragged tail block (the
    zero padding never dilutes a block's score); ``max`` is any-of.
    """
    L, n = u.shape
    nb = -(-n // block)
    up = jnp.pad(u, ((0, 0), (0, nb * block - n)))
    grouped = up.reshape(L, nb, block)
    if reduce == "mean":
        cnt = jnp.minimum(block, n - jnp.arange(nb) * block)
        return grouped.sum(-1) / cnt[None, :]
    return grouped.max(-1)


def _expand_blocks(bm: jax.Array, block: int, n: int) -> jax.Array:
    """Inverse of :func:`_pool_blocks` for 0/1 masks: block-constant (L, n)."""
    return jnp.repeat(bm, block, axis=-1)[..., :n]


@CT.contract(pre=_select_masks_pre)
def select_masks(scores: Dict[str, jax.Array],
                 forced: Dict[str, jax.Array],
                 volume: jax.Array,
                 p_s: float,
                 key: jax.Array,
                 block: int = 0) -> Dict[str, jax.Array]:
    """Eq. 2 across all unit types.  scores/forced: {key: (L, n)}.

    ``volume`` is the client's P (scalar in (0, 1], traced).  Returns masks
    {key: (L, n) float 0/1} with ~P*n ones per row.  Traced counts plus the
    explicit key argument make this directly vmap-able over a stacked client
    cohort (federated.runtime.BatchedFLRun vmaps the whole cycle).

    ``p_s`` interpolates the draw: 0.0 is pure random rotation (the Caldas
    baseline), 1.0 is pure score top-k (k_top == k_total, no random tail) —
    which is exactly FLuID's invariant-dropout selection, so the ``fluid``
    scheme reuses this function unchanged (federated.schemes._fluid_hcfg).

    ``block`` > 0 runs Eq. 2 at BLOCK granularity (beyond-paper, for the
    Pallas kernels): unit scores are mean-pooled per block, forced flags
    any-pooled, the top-k/random/forced draw picks ~P·(n/block) blocks, and
    the mask expands block-constant.  Rounding a unit-scattered selection
    UP instead (block_align_mask) degenerates to the full model — a block
    survives only with probability (1-P)^block — so selecting blocks is the
    version that keeps the compressed volume at P while staying
    structurally skippable.

    Pooling applies ONLY to unit types with n >= 4·block.  Block selection
    quantizes a layer's volume to the 1/nb grid with a floor of one block,
    so few-block layers would silently train far above P (one-of-two
    blocks = 50% minimum); requiring nb >= 4 bounds the grid at 1/4 —
    conv channels, attention heads, and tiny fc layers keep unit-granular
    Eq. 2 and their exact share of P, at the cost of no structural skip
    there (on TPU the layers that matter are 16+ blocks wide and their
    grid is fine).
    """
    if block:
        # a list, not a set: set order follows the per-process string hash,
        # which would reorder the traced program and its compile-cache key
        pooled = [k for k, u in scores.items()
                  if u.shape[-1] >= 4 * block]
        if not pooled:
            # nothing qualifies for pooling: fall straight through to the
            # unit-granular path on the ORIGINAL key, so mask_block > 0 on
            # a small model stays seed-compatible with mask_block = 0
            return select_masks(scores, forced, volume, p_s, key)
        bscores = {k: _pool_blocks(scores[k], block, "mean")
                   for k in pooled}
        bforced = {k: _pool_blocks(forced[k].astype(jnp.float32), block,
                                   "max").astype(bool)
                   for k in pooled if k in forced}
        # distinct subkeys per group: two unit types of equal size in
        # different groups must not share a selection stream
        bmasks = select_masks(bscores, bforced, volume, p_s,
                              jax.random.fold_in(key, 0xB10C))
        unit = select_masks({k: u for k, u in scores.items()
                             if k not in pooled},
                            {k: f for k, f in forced.items()
                             if k not in pooled}, volume, p_s,
                            jax.random.fold_in(key, 0x0A11))
        return {k: _expand_blocks(bmasks[k], block, scores[k].shape[-1])
                if k in pooled else unit[k] for k in scores}
    out = {}
    for i, (k, u) in enumerate(sorted(scores.items())):
        L, n = u.shape
        k_total = jnp.clip(jnp.round(volume * n).astype(jnp.int32), 1, n)
        k_top = jnp.round(p_s * k_total).astype(jnp.int32)
        rows = jax.vmap(_row_select, in_axes=(0, 0, None, None, 0))(
            u, forced.get(k, jnp.zeros_like(u, bool)), k_total, k_top,
            jax.random.split(jax.random.fold_in(key, i), L))
        out[k] = rows
    return out


def update_skip_counts(skip_counts: Dict[str, jax.Array],
                       masks: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """C_s: 0 when the unit joined this cycle, else +1."""
    return {k: jnp.where(masks[k] > 0, 0, skip_counts[k] + 1)
            for k in skip_counts}


def rotation_threshold(volume: jax.Array, auto: bool = True,
                       fixed: int = 4) -> jax.Array:
    """Section VI.A: threshold = 1 + m / sum(p_i n_i) = 1 + 1/P."""
    if not auto:
        return jnp.asarray(fixed, jnp.float32)
    return 1.0 + 1.0 / jnp.maximum(volume, 1e-3)


def forced_units(skip_counts: Dict[str, jax.Array],
                 threshold: jax.Array) -> Dict[str, jax.Array]:
    return {k: v.astype(jnp.float32) >= threshold for k, v in
            skip_counts.items()}


def init_skip_counts(schema: Dict[str, Tuple[int, int]]):
    return {k: jnp.zeros(s, jnp.int32) for k, s in schema.items()}


def init_scores(schema: Dict[str, Tuple[int, int]]):
    return {k: jnp.zeros(s, jnp.float32) for k, s in schema.items()}
