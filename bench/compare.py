"""The comparison that decides ``correct``.

The engine under test runs the cell's first ``check_rounds`` rounds during
set-up, through the same object and call that the measured window then
drives; the plain reference (``reference.py``) replays them from the same
seed.  Compared, each against its own limit (``limits/<cell>.json``):

* ``loss_gap``: per round, |engine loss - reference loss| / |reference
  loss|, the worst round (read by ``control.py``; compared only where a
  cell's limits file names it);
* ``update1_gap``: the first round's change of the global model, leaf by
  leaf: |norm(engine change) - norm(reference change)| over the larger of
  the reference leaf's norm and the median leaf's, the worst leaf;
* ``change3_gap``: the same for the change over all ``check_rounds``;
* ``mask_units_differ``: units on which a straggler's first-round Eq. 2
  mask differs from the reference's (exact: limit 0);
* ``selected_units_gap``: the worst gap, over clients and rounds, of the
  number of units a client trained, read from its selected fraction (the
  Eq. 10 weight) times the model's unit count (exact: limit 0).

Leaves whose first-round change in the reference is under a thousandth of
the median leaf's are left out of the two norm gaps: they move by rounding.
"""
from __future__ import annotations

import jax
import numpy as np

NAMES = ("loss_gap", "update1_gap", "change3_gap", "mask_units_differ",
         "selected_units_gap")


def leaves(tree, is_leaf=None) -> dict:
    """A tree's leaves by path (``"blocks/attn/wq"``); a flat dict keeps
    its keys."""
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): leaf for path, leaf in flat}


def host_leaves(tree) -> dict:
    """``leaves`` of a tree of device arrays, copied to the host as
    float32."""
    return {k: np.array(v, np.float32)
            for k, v in leaves(jax.device_get(tree)).items()}


def _norms(after: dict, before: dict) -> dict:
    return {k: float(np.linalg.norm((after[k].astype(np.float64)
                                     - before[k].astype(np.float64))))
            for k in before}


def leaf_gap(prog_after: dict, ref_after: dict, before: dict,
             keep: list) -> float:
    p, r = _norms(prog_after, before), _norms(ref_after, before)
    med = float(np.median([r[k] for k in keep]))
    return max(abs(p[k] - r[k]) / max(r[k], med) for k in keep)


def moving_leaves(ref_first: dict, before: dict) -> list:
    r = _norms(ref_first, before)
    med = float(np.median(list(r.values())))
    return sorted(k for k, v in r.items() if v >= 1e-3 * med)


def readings(prog: dict, ref: dict, before: dict, units: int) -> dict:
    """``prog``/``ref``: {"losses": [...], "ratios": [[...] per round],
    "masks": {cid: {unit type: (rows, n)}} of round 1, "params": [after
    round 1, after the last round]} (host float32, leaves by path);
    ``units``: the model's maskable units over every row."""
    keep = moving_leaves(ref["params"][0], before)
    differ = 0
    for cid, want in ref["masks"].items():
        got = prog["masks"][cid]
        differ += sum(int(np.sum(np.asarray(got[k]) != np.asarray(want[k])))
                      for k in want)
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "update1_gap": leaf_gap(prog["params"][0], ref["params"][0], before,
                                keep),
        "change3_gap": leaf_gap(prog["params"][-1], ref["params"][-1],
                                before, keep),
        "mask_units_differ": float(differ),
        "selected_units_gap": float(max(
            abs(round(a * units) - round(b * units))
            for pr, rr in zip(prog["ratios"], ref["ratios"])
            for a, b in zip(pr, rr))),
    }


def judge(values: dict, limits: dict) -> dict:
    """{name: {"value", "limit", "ok"}} in ``NAMES`` order, for the numbers
    that the cell's limits file names (a number that no control or fault
    can fail in a cell is not compared there)."""
    return {n: {"value": values[n], "limit": limits[n],
                "ok": bool(values[n] <= limits[n])}
            for n in NAMES if n in limits}


def replay(ref, rounds: int) -> dict:
    """Run the reference ``rounds`` rounds; collect what is compared."""
    out = {"losses": [], "ratios": [], "masks": {}, "params": []}
    for r in range(rounds):
        loss, ratios, masks = ref.round()
        out["losses"].append(loss)
        out["ratios"].append(ratios)
        if r == 0:
            out["masks"] = masks
            out["params"].append(ref.host_params())
    out["params"].append(ref.host_params())
    return out
