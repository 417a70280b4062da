"""Compile-only rehearsal: the main-path Pallas kernels at real widths,
compiled for a described TPU v5e chip (no chip attached).

Interpret mode runs the kernel bodies as plain JAX and accepts block shapes
the TPU's compiler refuses; these cases catch that without a chip.  Each
asserts the compiled program holds a Mosaic kernel (``tpu_custom_call``).
The topology is described inside a fixture, never while a module is being
imported: only one process at a time may load the TPU compiler's library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.masked_matmul import masked_matmul, masked_matmul_dk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel", [masked_matmul, masked_matmul_dk],
                         ids=["n_blocks", "k_blocks"])
@pytest.mark.parametrize("m,k,n", [
    (128, 4096, 1024),           # AlexNet fc0: 8 mask blocks
    (128, 4096, 11136),          # dense-LM MLP, 11008 padded to 87 blocks
], ids=["alexnet_fc0", "lm_mlp"])
def test_masked_matmul_compiles(one_chip, kernel, m, k, n):
    if kernel is masked_matmul_dk:          # dx = dy @ w.T: K is masked
        k, n = n, k
    flags = (n if kernel is masked_matmul else k) // 128
    text = _compiled_text(kernel, one_chip, ((m, k), jnp.float32),
                          ((k, n), jnp.float32), ((flags,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cohort", [0, 4], ids=["one_client", "vmapped"])
def test_masked_dense_value_and_grad_compiles(one_chip, monkeypatch, cohort):
    # the platform the test runs on is the CPU; steer the kernels native
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def loss(x, w, mask):
        return jnp.sum(ops.masked_dense(x, w, mask, impl=ops.PALLAS,
                                        block_n=128) ** 2)

    fn = jax.value_and_grad(loss, argnums=(0, 1))
    lead = (cohort,) if cohort else ()
    if cohort:              # the batched engines vmap a straggler cohort,
        fn = jax.vmap(fn, in_axes=(0, None, 0))   # one mask per client
    text = _compiled_text(fn, one_chip, (lead + (128, 4096), jnp.float32),
                          ((4096, 1024), jnp.float32),
                          (lead + (1024,), jnp.float32))
    assert text.count("tpu_custom_call") >= 3   # fwd, dx and dw kernels


def test_flash_attention_causal_compiles(one_chip):
    shape = ((1, 8, 2048, 128), jnp.float32)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True), one_chip,
        shape, shape, shape)
    assert "tpu_custom_call" in text
