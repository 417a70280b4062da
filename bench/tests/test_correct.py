"""The comparison that decides ``correct``, driven through the rest of a run
at a size the CPU can hold: a sound run passes; the control (the reference
in bfloat16 in the engine's place) and each fault that a training cell can
have, planted in the engine, fail.  The same for the tiny decoder
(``data/tiny-decoder.json``), which has no cell: it proves that a token LM
goes through the harness as a configuration and its module alone."""
import json
import os
import subprocess
import sys

import pytest

from bench import compare, control, run
from bench.world import read_json

DATA = os.path.join(os.path.dirname(__file__), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = {"name": "alexnet.sync-stragglers", "config": "alexnet-cifar10",
        "traffic": "sync32-stragglers", "chips": 1}


def _tiny(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)


def _run(cell=CELL, traffic="tiny-sync"):
    return run.run_cell(run.load_benchmark(), cell, 2 ** 33 + 5, 1.0, False,
                        _tiny("tiny-alexnet"), _tiny(traffic))


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"round_s", "round_p90_s", "setup_s"}
    limits = read_json("limits", CELL["name"] + ".json")
    assert list(res["checks"]) == [n for n in compare.NAMES if n in limits]


def test_control_fails():
    got = control.reading(CELL, 17, "bf16", _tiny("tiny-alexnet"),
                          _tiny("tiny-sync"))
    limits = read_json("limits", CELL["name"] + ".json")
    judged = compare.judge(got, limits)
    assert not all(c["ok"] for c in judged.values()), judged


def _stuck(monkeypatch):
    from repro.federated import BatchedFLRun
    train = BatchedFLRun._train_cohort

    def stuck(self, cohort, cclients):
        before = self.global_params
        out = train(self, cohort, cclients)
        self.global_params = before
        return out

    monkeypatch.setattr(BatchedFLRun, "_train_cohort", stuck)


def _half_batch(monkeypatch):
    from repro.federated.adapter import CNNAdapter, TokenLMAdapter
    for cls in (CNNAdapter, TokenLMAdapter):
        def half(self, params, batch, masks, loss=cls.loss_fn):
            keep = next(iter(batch.values())).shape[0] // 2
            return loss(self, params,
                        {k: v[:keep] for k, v in batch.items()}, masks)

        monkeypatch.setattr(cls, "loss_fn", half)


@pytest.mark.parametrize("plant", [_stuck, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_fails(monkeypatch, plant):
    plant(monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]


DECODER = {"name": "tiny-decoder", "config": "tiny-decoder", "chips": 1}
#: the program masks a head's query (q * mask), not its output: a masked
#: head still sends the causal mean of its values through wo, and its wv
#: and wo train.  The plain reference, which multiplies the head's output
#: as the published mechanism has it, then departs in every straggler's
#: update (update1_gap 0.044, change3_gap 0.067, loss_gap 5.1e-4 at seed
#: 2**33 + 5); with the mask moved onto q in the reference the gaps read
#: 2e-8 to 4e-8.
Q_MASKED_HEADS = pytest.mark.xfail(
    strict=True, reason="the program masks a head's q, not its output")


def _decoder_run(traffic, seed=2 ** 33 + 5):
    return run.run_cell(run.load_benchmark(), DECODER, seed, 1.0, False,
                        _tiny("tiny-decoder"), _tiny(traffic),
                        _tiny("tiny-decoder.limits"))


@pytest.mark.parametrize("traffic", [
    "tiny-token-sync",
    pytest.param("tiny-token-stragglers", marks=Q_MASKED_HEADS)])
def test_token_lm_sound_run_is_correct(traffic):
    res = _decoder_run(traffic)
    assert list(res["checks"]) == list(compare.NAMES)
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("traffic", ["tiny-token-sync",
                                     "tiny-token-stragglers"])
def test_token_lm_control_fails(traffic):
    got = control.reading(DECODER, 17, "bf16", _tiny("tiny-decoder"),
                          _tiny(traffic))
    judged = compare.judge(got, _tiny("tiny-decoder.limits"))
    assert not all(c["ok"] for c in judged.values()), judged


@pytest.mark.parametrize("plant", [_stuck, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_token_lm_fault_fails(monkeypatch, plant):
    plant(monkeypatch)
    res = _decoder_run("tiny-token-sync")
    assert not res["correct"], res["checks"]


SHARDED = """
import json, sys
sys.path[:0] = {path!r}
import jax
from bench import run
cell = {cell!r}
tiny = [json.load(open(p)) for p in {files!r}]
sound = run.run_cell(run.load_benchmark(), cell, 3, 1.0, False, *tiny)
jax.lax.psum = lambda x, axis_name, **kw: x
cut = run.run_cell(run.load_benchmark(), cell, 3, 1.0, False, *tiny)
print(json.dumps([sound["correct"], cut["correct"], sound["checks"],
                  cut["checks"]]))
"""


def test_exchange_between_chips_left_out_fails():
    """Four host devices; the sharded engine's psum made the identity."""
    cell = {"name": "alexnet.population-sharded", "config": "alexnet-cifar10",
            "traffic": "population1024-sharded", "chips": 4}
    files = [os.path.join(DATA, "tiny-alexnet.json"),
             os.path.join(DATA, "tiny-sharded.json")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(path=[ROOT, os.path.join(ROOT, "src")], cell=cell,
                          files=files)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    sound, cut, *checks = json.loads(out.stdout.strip().splitlines()[-1])
    assert sound, checks[0]
    assert not cut, checks[1]
