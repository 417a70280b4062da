"""Sharding rule engine: divisibility fallbacks, cache specs, batch specs."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.parallel import sharding as SH


@pytest.fixture(scope="module")
def mesh():
    # AbstractMesh: the 16x16 production topology without real devices
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def test_spec_divisible(mesh):
    # default rules: FSDP on embed (data axis) + TP on mlp (model axis)
    spec = SH.spec_for_axes(("embed", "mlp"), (64, 128 * 16), mesh)
    assert spec == P("data", "model")
    # indivisible embed replicates
    spec2 = SH.spec_for_axes(("embed", "mlp"), (50, 128 * 16), mesh)
    assert spec2 == P(None, "model")


def test_spec_fallback_indivisible(mesh):
    # 14 heads don't divide the 16-way model axis -> replicate (internvl2)
    spec = SH.spec_for_axes(("embed", "heads", "head_dim"), (896, 14, 64),
                            mesh)
    assert spec[1] is None


def test_internvl2_mlp_still_shards(mesh):
    # d_ff = 4864 = 16*304 -> tensor-sharded even though heads replicate
    spec = SH.spec_for_axes(("embed", "mlp"), (896, 4864), mesh)
    assert spec[1] == "model"


def test_no_axis_reuse_within_tensor(mesh):
    spec = SH.spec_for_axes(("mlp", "heads"), (128 * 16, 16 * 16), mesh,
                            rules={"mlp": ("model",), "heads": ("model",)})
    assert spec == P("model", None)


def test_rules_for_small_vs_large():
    small = SH.rules_for(ARCHS["xlstm-125m"])
    big = SH.rules_for(ARCHS["qwen2.5-32b"])
    assert small["embed"] == ()
    assert big["embed"] == ("data",)


def test_cache_spec_batch_then_kv(mesh):
    spec = SH.cache_spec((128, 1024, 32, 64), mesh, batch=128, seq=1024,
                         kv_heads=32)
    assert spec[0] is not None
    assert spec[2] == "model"


def test_cache_spec_gqa_fallback_seq_model(mesh):
    # kv=8 < 16-way model axis -> cache sequence absorbs "model"
    spec = SH.cache_spec((128, 32768, 8, 64), mesh, batch=128, seq=32768,
                         kv_heads=8)
    assert spec[1] == "model"


def test_cache_spec_long_context_seq_sharded(mesh):
    # batch=1 (long_500k): sequence takes the data axes
    spec = SH.cache_spec((1, 1024 * 16, 8, 64), mesh, batch=1,
                         seq=1024 * 16, kv_heads=8)
    assert spec[1] is not None


def test_batch_spec(mesh):
    spec = SH.batch_spec((256, 128), mesh, batch_size=256)
    assert spec[0] is not None


def test_batch_spec_indivisible_replicates(mesh):
    spec = SH.batch_spec((3, 128), mesh, batch_size=3)
    assert spec == P(None, None)
