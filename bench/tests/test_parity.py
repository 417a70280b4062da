"""The harness reproduces, bit for bit, what it produced before each
configuration's model code moved into its own module.

``data/parity.json`` holds, for every tiny world that ``bench/tests``
builds, the SHA-256 of its data, parts and initial weights, and the plain
reference's replay of the set-up rounds (losses, ratios, masks, and each
leaf's norm and SHA-256 after rounds 1 and 3); and ``RoundCost`` at the
published AlexNet and ResNet-18 configurations with fixed masks.  All of
it belongs to the benchmark, none to the program under test.  It was
written by this file's ``record`` from the tree before that move:

    JAX_PLATFORMS=cpu python bench/tests/test_parity.py

and is compared here with what the same function reads from today's tree.
"""
import hashlib
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
GOLDEN = os.path.join(HERE, "data", "parity.json")
SEED = 2 ** 33 + 5
WORLDS = [(c, t) for c in ("tiny-alexnet", "tiny-resnet18")
          for t in ("tiny-sync", "tiny-sharded")]


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _leaves(tree) -> dict:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in flat}


def _tree_sha(tree) -> dict:
    return {k: _sha([v]) for k, v in sorted(_leaves(tree).items())}


def _replay_record(out: dict) -> dict:
    return {"losses": out["losses"], "ratios": out["ratios"],
            "masks": {str(c): _sha([np.asarray(m[k]).reshape(-1)
                                    for k in sorted(m)])
                      for c, m in sorted(out["masks"].items())},
            "params": [{k: [float(np.linalg.norm(v.astype(np.float64))),
                            _sha([v])]
                        for k, v in sorted(_leaves(p).items())}
                       for p in out["params"]]}


def _fixed_masks(traffic: dict, model_units: dict, stragglers):
    """Block-constant masks drawn once from a fixed stream: the first unit
    block of every type stays alive, so no layer is empty."""
    rng = np.random.default_rng(1234)
    block = traffic["mask_block"]
    out = {}
    for i in stragglers:
        m = {}
        for k, n in model_units.items():
            nb = -(-n // block)
            alive = rng.random(nb) < 0.4
            alive[0] = True
            m[k] = np.repeat(alive.astype(np.float32), block)[:n]
        out[i] = m
    return out


def _cost_record(cfg_name: str, traffic_name: str) -> dict:
    from bench import flops, models
    cfg = _read(ROOT, "bench", "configs", cfg_name + ".json")
    traffic = _read(ROOT, "bench", "traffic", traffic_name + ".json")
    units = {k: int(np.prod(v))
             for k, v in models.load(cfg["model"]).mask_units(cfg).items()}
    n = traffic["capable"] + traffic["stragglers"]
    rng = np.random.default_rng(99)
    k = traffic["participation"] or n
    cohorts = [sorted(int(i) for i in rng.choice(n, size=k, replace=False))
               for _ in range(3)]
    strag = sorted({i for c in cohorts for i in c
                    if i >= traffic["capable"]})
    masks = _fixed_masks(traffic, units, strag)
    cost = flops.RoundCost(types.SimpleNamespace(cfg=cfg, traffic=traffic),
                           cohorts, masks)
    return {"train_ops": cost.train_ops, "eval_ops": cost.eval_ops,
            "ops_per_round": cost.ops_per_round,
            "kernel_ops": cost.kernel_ops,
            "kernel_bytes": cost.kernel_bytes}


def record_world(cname: str, tname: str) -> dict:
    from bench import compare
    from bench.reference import Reference
    from bench.world import make_world

    cfg = _read(HERE, "data", cname + ".json")
    traffic = _read(HERE, "data", tname + ".json")
    world = make_world({"name": "parity"}, SEED, cfg, traffic)
    rec = {"train": _tree_sha(world.train), "test": _tree_sha(world.test),
           "parts": _sha(world.parts), "fleet": world.fleet,
           "weights": _tree_sha(world.weights)}
    ref = compare.replay(Reference(world), traffic["check_rounds"])
    rec["reference"] = _replay_record(ref)
    return json.loads(json.dumps(rec))


COSTS = [("alexnet-cifar10", "sync32-stragglers"),
         ("alexnet-cifar10", "sync32-all-capable"),
         ("alexnet-cifar10", "population1024-sharded"),
         ("resnet18-cifar100", "sync16x2-stragglers")]


def record() -> dict:
    return {"seed": SEED,
            "worlds": {f"{c}/{t}": record_world(c, t) for c, t in WORLDS},
            "round_cost": {f"{c}/{t}": _cost_record(c, t) for c, t in COSTS}}


@pytest.mark.parametrize("cname,tname", WORLDS)
def test_world_matches_golden(cname, tname):
    want = _read(GOLDEN)["worlds"][f"{cname}/{tname}"]
    got = record_world(cname, tname)
    for part in want:
        assert got[part] == want[part], part
    assert got == want


@pytest.mark.parametrize("cname,tname", COSTS)
def test_round_cost_matches_golden(cname, tname):
    want = _read(GOLDEN)["round_cost"][f"{cname}/{tname}"]
    assert _cost_record(cname, tname) == want


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with open(GOLDEN, "w") as f:
        json.dump(record(), f, indent=1, sort_keys=True)
        f.write("\n")
