"""Differentiable, padding-safe public wrappers for the Pallas kernels.

This module is the EXECUTION SEAM for kernel-backed soft-training: the model
layers call :func:`masked_dense` / :func:`masked_contract` /
:func:`flash_attention` with ``impl="pallas" | "reference"`` and get

* identical numerics either way (the pallas path multiplies by the unit mask
  so it is exact for ANY 0/1 mask, not just block-aligned ones — dead blocks
  are additionally SKIPPED on the MXU, which is where the Helios volume
  fraction P turns into wall-clock);
* a ``jax.custom_vjp`` on the pallas path whose backward ALSO skips dead
  column blocks (dx via a contraction-masked kernel over dy·mask, dw via the
  column-masked kernel), with EXACTLY-ZERO gradients for masked-out columns
  — the frozen-neuron semantics Helios soft-training requires.

Shapes are padded up to block multiples internally (zero columns are dead
blocks and get skipped), so callers never hit divisibility asserts; unit
masks of any length are handled by :func:`block_align_mask`-style padding.

On CPU kernels execute with ``interpret=True`` — the kernel body runs as
traced JAX ops, bit-compatible semantics for correctness tests.  On TPU they
compile natively; :func:`_interpret` derives the mode from the backend and
refuses any other platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.analysis import contracts as CT
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.masked_matmul import masked_matmul as _mm
from repro.kernels.masked_matmul import masked_matmul_dk as _mm_dk
from repro.kernels.ssd_scan import ssd_diag as _ssd_diag

#: canonical dispatch values for the ``kernels`` / ``impl`` knobs
PALLAS = "pallas"
REFERENCE = "reference"


def _interpret() -> bool:
    """Kernel execution mode, decided by the platform: the CPU runs the
    kernel bodies in interpret mode (tests), the TPU compiles them with
    Mosaic.  Any other platform has no Pallas-TPU lowering, so it raises
    rather than silently taking one path or the other."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run on 'cpu' (interpret mode) or "
                       f"'tpu' (native); this process's backend is "
                       f"{backend!r}")


def _free_block(n: int, cap: int = 128) -> int:
    """Tile size for axes with no mask structure.

    Interpret mode (CPU) has no alignment constraints, so small/ragged dims
    get one exact-size tile (no padding waste).  Native Mosaic compilation
    requires hardware-aligned tiles — there the full ``cap`` (128, lane- and
    sublane-aligned) is used and :func:`_pad_axis` rounds the operand up.
    """
    if not _interpret():
        return cap
    return min(cap, max(n, 1))


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# block-aligned masks
# ---------------------------------------------------------------------------


def block_align_mask(unit_mask: jax.Array, block_n: int) -> jax.Array:
    """Round a Helios unit mask UP to block granularity (beyond-paper:
    block-aligned selection keeps the MXU dense within live blocks).

    Idempotent; output is a superset of the input mask and block-constant
    (every length-``block_n`` group of the padded mask is all-0 or all-1) —
    properties pinned by tests/test_kernel_softtrain.py.
    """
    n = unit_mask.shape[-1]
    nb = (n + block_n - 1) // block_n
    pad = nb * block_n - n
    m = jnp.pad(unit_mask, [(0, 0)] * (unit_mask.ndim - 1) + [(0, pad)])
    blocks = m.reshape(m.shape[:-1] + (nb, block_n)).max(axis=-1)
    out = jnp.repeat(blocks, block_n, axis=-1)
    return out[..., :n]


def _block_alive(unit_mask: jax.Array, block_n: int) -> jax.Array:
    """(N,) 0/1 mask -> (ceil(N/bn),) per-block alive flags (a block with ANY
    live unit runs; padding columns are dead)."""
    m = _pad_axis(unit_mask, 0, block_n)
    return m.reshape(-1, block_n).max(axis=1)


# ---------------------------------------------------------------------------
# masked matmul (column-block skip) + its VJP
# ---------------------------------------------------------------------------


def _mm_padded(x, w, unit_mask, block_n, name="masked_matmul_fwd"):
    """Column-masked kernel over padded operands; exact ``x @ (w·mask)``.
    ``name`` tells the forward call from the backward one in a trace."""
    m, k = x.shape
    n = w.shape[1]
    bm, bk = _free_block(m), _free_block(k)
    xp = _pad_axis(_pad_axis(x, 0, bm), 1, bk)
    wp = _pad_axis(_pad_axis(w, 0, bk), 1, block_n)
    alive = _block_alive(unit_mask, block_n)
    y = _mm(xp, wp, alive, block_m=bm, block_n=block_n, block_k=bk,
            interpret=_interpret(), name=name)[:m, :n]
    # multiply by the unit mask: restores exactness for masks that are not
    # block-constant (a live block may still contain dead units) and pins
    # dead columns to bit-zero even on the padded path
    return y * unit_mask.astype(y.dtype)[None, :]


def _mm_dk_padded(x, w, unit_mask, block_n):
    """Contraction-masked kernel: ``x @ w`` skipping dead K-blocks.  Exact
    when the skipped columns of ``x`` are zero (masked activations or
    masked cotangents)."""
    m, k = x.shape
    n = w.shape[1]
    bm, bn = _free_block(m), _free_block(n)
    xp = _pad_axis(_pad_axis(x, 0, bm), 1, block_n)
    wp = _pad_axis(_pad_axis(w, 0, block_n), 1, bn)
    alive = _block_alive(unit_mask, block_n)
    return _mm_dk(xp, wp, alive, block_m=bm, block_n=bn, block_k=block_n,
                  interpret=_interpret())[:m, :n]


@functools.lru_cache(maxsize=None)
def _masked_dense_pallas(block_n: int):
    """custom_vjp'd ``y = x @ (w · mask)`` at one mask-block granularity.

    Backward: dx = (dy·mask) @ Wᵀ with dead N-blocks skipped in the
    contraction; dw = Xᵀ @ (dy·mask) with dead column blocks skipped and
    masked columns EXACTLY zero.  The mask itself gets a zero cotangent
    (selection is not differentiable).
    """

    @jax.custom_vjp
    def fn(x, w, unit_mask):
        return _mm_padded(x, w, unit_mask, block_n)

    def fwd(x, w, unit_mask):
        return fn(x, w, unit_mask), (x, w, unit_mask)

    def bwd(res, dy):
        x, w, unit_mask = res
        dym = dy * unit_mask.astype(dy.dtype)[None, :]
        dx = _mm_dk_padded(dym, w.T, unit_mask, block_n)
        dw = _mm_padded(x.T, dym, unit_mask, block_n, "masked_matmul_bwd")
        return dx, dw, jnp.zeros_like(unit_mask)

    fn.defvjp(fwd, bwd)
    return fn


@functools.lru_cache(maxsize=None)
def _masked_contract_pallas(block_n: int):
    """custom_vjp'd ``y = h @ w`` where the CONTRACTION dim is unit-masked.

    Exact whenever masked columns of ``h`` are zero (guaranteed when ``h``
    came through :func:`masked_dense`).  Backward: dh = dy @ Wᵀ with masked
    columns zeroed (they are dead downstream anyway — zeroing keeps the
    skip structural); dw = hᵀ @ dy with dead ROW blocks skipped and masked
    rows exactly zero.
    """

    @jax.custom_vjp
    def fn(h, w, unit_mask):
        return _mm_dk_padded(h * unit_mask.astype(h.dtype)[None, :], w,
                             unit_mask, block_n)

    def fwd(h, w, unit_mask):
        return fn(h, w, unit_mask), (h, w, unit_mask)

    def bwd(res, dy):
        h, w, unit_mask = res
        # dh = dy @ wᵀ, masked columns (dh's N axis = the masked dim) zeroed
        dh = _mm_padded(dy, w.T, unit_mask, block_n, "masked_matmul_bwd")
        # dw = hᵀ @ dy, rows = masked dim: compute dwᵀ with the column-masked
        # kernel, so dead rows of dw are skipped AND exactly zero
        dw = _mm_padded(dy.T, h, unit_mask, block_n,
                        "masked_matmul_bwd").T
        return dh, dw, jnp.zeros_like(unit_mask)

    fn.defvjp(fwd, bwd)
    return fn


def _collapse(x):
    """(..., K) -> (M, K) view + a restorer for the leading dims."""
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lambda y: y.reshape(lead + y.shape[-1:])


def _masked_dense_pre(x, w, unit_mask, **kwargs):
    """Kernel precondition (shape-level, jit-safe): x (..., K) contracts
    with w (K, N); unit_mask masks w's OUTPUT axis (N,)."""
    if w.ndim != 2 or x.shape[-1] != w.shape[0]:
        raise CT.ContractError(
            f"masked_dense: x (..., K={x.shape[-1]}) incompatible with "
            f"w {w.shape} (want (K, N))")
    if unit_mask.shape != (w.shape[1],):
        raise CT.ContractError(
            f"masked_dense: unit_mask {unit_mask.shape} must be "
            f"(N,) = ({w.shape[1]},) — it masks w's output axis")


@CT.contract(pre=_masked_dense_pre)
def masked_dense(x, w, unit_mask, *, impl: str = REFERENCE,
                 block_n: int = 128):
    """Soft-training dense layer: ``y = x @ (w · unit_mask[None, :])``.

    x: (..., K); w: (K, N); unit_mask: (N,) float 0/1.  ``impl="pallas"``
    runs the block-sparse kernel pair (fwd+bwd skip dead column blocks);
    ``impl="reference"`` is the plain-jnp semantics the kernels are pinned
    against.  Masked columns of y — and of every gradient — are exactly 0.
    """
    if impl != PALLAS:
        return x @ (w * unit_mask.astype(w.dtype)[None, :])
    x2, restore = _collapse(x)
    return restore(_masked_dense_pallas(block_n)(x2, w, unit_mask))


def _masked_contract_pre(h, w, unit_mask, **kwargs):
    """Kernel precondition: h (..., N) contracts with w (N, K) over the
    MASKED axis; unit_mask is (N,)."""
    if w.ndim != 2 or h.shape[-1] != w.shape[0]:
        raise CT.ContractError(
            f"masked_contract: h (..., N={h.shape[-1]}) incompatible "
            f"with w {w.shape} (want (N, K))")
    if unit_mask.shape != (w.shape[0],):
        raise CT.ContractError(
            f"masked_contract: unit_mask {unit_mask.shape} must be "
            f"(N,) = ({w.shape[0]},) — it masks the contraction axis")


@CT.contract(pre=_masked_contract_pre)
def masked_contract(h, w, unit_mask, *, impl: str = REFERENCE,
                    block_n: int = 128):
    """Second half of a masked MLP: ``y = (h · unit_mask) @ w`` where the
    contraction dimension is the masked one.  h: (..., N); w: (N, K);
    unit_mask: (N,).  The pallas path skips dead contraction blocks in the
    forward and dead rows of dw in the backward (exact zeros)."""
    if impl != PALLAS:
        return (h * unit_mask.astype(h.dtype)) @ w
    h2, restore = _collapse(h)
    return restore(_masked_contract_pallas(block_n)(h2, w, unit_mask))


def masked_matmul(x, w, unit_mask, *, block_n: int = 128):
    """Soft-training matmul: y = x @ (w * unit_mask), block-sparse skip.

    unit_mask: (N,) 0/1 of ANY length — masks whose length is not a multiple
    of ``block_n`` are padded (zero-padding = dead blocks), not rejected,
    and masks that are not block-constant stay exact because the kernel
    output is multiplied by the unit mask.  Block-aligned selection
    (:func:`block_align_mask`) makes the skip structural.  The M/K tile
    sizes are derived from the shapes (:func:`_free_block`).
    """
    return _mm_padded(x, w, unit_mask, block_n)


# ---------------------------------------------------------------------------
# flash attention + recompute VJP
# ---------------------------------------------------------------------------


def _flash_padded(q, k, v, causal, block_q, block_k):
    """Kernel forward with the sequence axes padded to block multiples.

    q, k, v: (B, H, S, hd).  Padded KEYS sit at the end of the sequence, so
    under the causal mask (with Sq == Sk, the self-attention training case)
    no real query ever attends one; padded QUERY rows are sliced off.  A
    causal CROSS-length call would let trailing queries attend zero-padded
    keys, so it is rejected.  (The non-causal path only pads queries.)
    """
    b, h, sq, hd = q.shape
    sk = k.shape[2]
    bq = _free_block(sq, block_q)
    bk = _free_block(sk, block_k)
    qp = _pad_axis(q, 2, bq)
    if causal:
        assert sq == sk, (
            f"causal flash kernel needs Sq == Sk (got {sq} vs {sk}): with "
            "key padding a trailing query would attend padded keys")
        kp, vp = _pad_axis(k, 2, bk), _pad_axis(v, 2, bk)
    else:
        assert sk % bk == 0, (
            f"non-causal flash kernel needs Sk % {bk} == 0 (got {sk}): "
            "padded keys would receive attention weight")
        kp, vp = k, v
    out = _flash(qp, kp, vp, causal=causal, block_q=bq, block_k=bk,
                 interpret=_interpret())
    return out[:, :, :sq]


@functools.lru_cache(maxsize=None)
def _flash_diff(causal: bool, block_q: int, block_k: int):
    """custom_vjp'd flash attention: pallas forward, checkpointed-recompute
    backward (the reference attention is re-evaluated and differentiated —
    O(S²) scores live only inside the VJP, never across it; a native Pallas
    backward kernel is the remaining TPU optimization)."""
    from repro.kernels import ref

    @jax.custom_vjp
    def fn(q, k, v):
        return _flash_padded(q, k, v, causal, block_q, block_k)

    def fwd(q, k, v):
        return fn(q, k, v), (q, k, v)

    def bwd(res, dy):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: ref.flash_attention_ref(q_, k_, v_,
                                                       causal=causal),
            q, k, v)
        return vjp(dy)

    fn.defvjp(fwd, bwd)
    return fn


def _flash_attention_pre(q, k, v, *, causal: bool = True, **kwargs):
    """Attention precondition: (B, H, S, hd) operands, matching k/v
    sequence lengths, and Sq == Sk under the causal mask (key padding
    would otherwise leak attention onto padded keys)."""
    if not (q.ndim == k.ndim == v.ndim == 4):
        raise CT.ContractError(
            f"flash_attention: q/k/v must be (B, H, S, hd), got "
            f"{q.shape}/{k.shape}/{v.shape}")
    if k.shape != v.shape or q.shape[:2] != k.shape[:2] or \
            q.shape[3] != k.shape[3]:
        raise CT.ContractError(
            f"flash_attention: incompatible q {q.shape} vs k {k.shape} "
            f"vs v {v.shape}")
    if causal and q.shape[2] != k.shape[2]:
        raise CT.ContractError(
            f"flash_attention: causal needs Sq == Sk "
            f"(got {q.shape[2]} vs {k.shape[2]})")


@CT.contract(pre=_flash_attention_pre)
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """q,k,v: (B, H, S, hd) -> (B, H, S, hd).  Differentiable (recompute
    VJP) and padding-safe: any SELF-attention length works under ``causal``
    (Sq == Sk required there; non-causal allows cross-length but needs
    block-aligned keys)."""
    return _flash_diff(causal, block_q, block_k)(q, k, v)


def ssd_diag(cr, br, cum, dtx):
    return _ssd_diag(cr, br, cum, dtx, interpret=_interpret())
