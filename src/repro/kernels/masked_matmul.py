"""Block-sparse masked matmul — the TPU-native soft-training hot spot.

Paper semantics: a straggler trains only the selected hidden units, i.e.
``y = x @ (W * unit_mask[None, :])``.  A 0/1 mask saves nothing on the MXU,
so the TPU adaptation makes the sparsity STRUCTURAL: Helios selection is
block-aligned (units chosen in groups of ``block_n``, a beyond-paper
optimization recorded in DESIGN.md §2), and this kernel SKIPS whole masked
column blocks: the (bm, bn) output tile for a dead block is written as zeros
without running the MXU, so compute drops by the volume fraction P — the
paper's edge-device speedup mechanism re-expressed for the MXU.  The
BlockSpec pipeline still copies a dead block's operands into VMEM, so HBM
traffic does not drop yet.

Grid: (M/bm, N/bn, K/bk), K innermost for accumulation.  ``block_alive`` is
a precomputed flag vector (mask.reshape(-1, bn).any(1)), scalar-prefetched
into SMEM so every grid point reads its own flag.

One kernel body serves both directions of the soft-training VJP — only the
grid axis the alive flag indexes differs:

* ``masked_matmul`` — flags index the OUTPUT-COLUMN (N) blocks: dead
  columns of y are written as zeros (the forward pass, and dw in the
  backward).
* ``masked_matmul_dk`` — flags index the CONTRACTION (K) blocks: dx =
  dy @ Wᵀ skipping K-blocks whose columns were masked out of the forward —
  exact whenever the skipped operand rows are zero, which the masked
  forward guarantees (dead columns of y, hence of dy·mask, are zero).
  Together the two make fwd AND bwd scale with the volume fraction P.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(alive_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int,
            alive_axis: int):
    """One (bm, bn) output tile; K-blocks arrive sequentially (innermost).
    ``alive_ref`` is the whole flag vector, scalar-prefetched into SMEM;
    ``alive_axis`` names the grid axis that indexes it (1 = N, 2 = K)."""
    k_idx = pl.program_id(2)
    alive = alive_ref[pl.program_id(alive_axis)] != 0

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(alive)
    def _mac():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _call(x, w, block_alive, alive_axis, block_m, block_n, block_k,
          interpret, name):
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, \
        (x.shape, w.shape, block_m, block_n, block_k)
    n_k = k // block_k
    # the flags ride in as a scalar-prefetch operand (SMEM), not as a rank-1
    # VMEM block: Mosaic refuses a (1,) block of a longer vector
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // block_m, n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk, a: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk, a: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, a: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, alive_axis=alive_axis),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        name=name,
    )(block_alive.astype(jnp.int32), x, w)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k",
                                    "interpret", "name"))
def masked_matmul(x: jax.Array, w: jax.Array, block_alive: jax.Array,
                  *, block_m: int = 128, block_n: int = 128,
                  block_k: int = 128, interpret: bool = False,
                  name: str = "masked_matmul") -> jax.Array:
    """y = x @ w with dead column-blocks skipped.

    x: (M, K); w: (K, N); block_alive: (N // block_n,) int32/bool.
    Masked-out columns of the result are ZERO (matching W*mask semantics
    when the mask is block-aligned).  ``name`` names the kernel call in the
    compiled program and the device trace (it must contain
    ``masked_matmul``).
    """
    return _call(x, w, block_alive, 1, block_m, block_n, block_k,
                 interpret, name)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k",
                                    "interpret"))
def masked_matmul_dk(x: jax.Array, w: jax.Array, block_alive: jax.Array,
                     *, block_m: int = 128, block_n: int = 128,
                     block_k: int = 128, interpret: bool = False) -> jax.Array:
    """y = x @ w with dead CONTRACTION (K) blocks skipped.

    x: (M, K); w: (K, N); block_alive: (K // block_k,) int32/bool.  Exact
    equality with the dense product requires the skipped blocks' operand
    entries to be zero (true for masked-gradient cotangents dy·mask and for
    masked hidden activations h·mask).
    """
    return _call(x, w, block_alive, 2, block_m, block_n, block_k,
                 interpret, "masked_matmul_dk")
