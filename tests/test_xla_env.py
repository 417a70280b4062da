"""Process-level JAX settings: XLA_FLAGS edits append, the compile cache
is placed from outside, and the benchmark harness refuses to start a
worker that would need a device this process already holds."""
import importlib.util
import os

import jax
import pytest

from repro import xla_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_xla_flags_append_never_overwrite(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=somewhere")
    xla_env.force_host_devices(4)
    assert os.environ["XLA_FLAGS"].split() == [
        "--xla_dump_to=somewhere", "--xla_force_host_platform_device_count=4"]
    monkeypatch.delenv("XLA_FLAGS")
    xla_env.force_host_devices(2)
    assert os.environ["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=2"


@pytest.fixture
def cache_dir_restored():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_from_env_sets_nothing(monkeypatch, cache_dir_restored):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert xla_env.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert xla_env.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def _bench_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_worker_refused_while_parent_holds_the_chip(monkeypatch):
    from jax._src import xla_bridge
    run = _bench_run()
    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: True)
    monkeypatch.setattr(run.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="already holds the tpu device"):
        run._run_worker("benchmarks.sharded_worker", [], {}, timeout=1)
    # a worker pinned to the CPU never needs the chip: it is started
    with pytest.raises(RuntimeError, match="exited 2"):   # bad args
        run._run_worker("benchmarks.sharded_worker", ["--no-such-flag"],
                        dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)


_TRACE_SELECTION = """
import jax, jax.numpy as jnp
from repro.core.selection import select_masks
units = {f"unit{i}": jnp.zeros((1, 64)) for i in range(6)}
forced = {k: jnp.zeros((1, 64), bool) for k in units}
print(jax.make_jaxpr(lambda s, f, v, key: select_masks(
    s, f, v, 0.1, key, block=16))(units, forced, jnp.float32(0.5),
                                  jax.random.PRNGKey(0)))
"""


def test_traced_selection_independent_of_hash_seed():
    """The persistent compile cache keys on the traced program, so block
    selection must trace the same program in every process, whatever the
    string-hash seed."""
    import subprocess
    import sys
    jaxprs = {subprocess.run(
        [sys.executable, "-c", _TRACE_SELECTION], capture_output=True,
        text=True, check=True, timeout=120,
        env=dict(os.environ, PYTHONHASHSEED=str(seed),
                 JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.path.join(ROOT, "src"))).stdout
        for seed in (1, 2, 3)}
    assert len(jaxprs) == 1
