"""The numbers that decide ``correct``, each against a limit of its own.

A step here is one mega-batch: one call of the trainer's
``run_megabatch``, its lockstep rounds, and the merge that closes it.

* ``loss_gap``: the largest, over the checked mega-batches, of the relative
  gap between the program's training loss and the reference's.
* ``update1_gap`` / ``update3_gap``: by the worst leaf, the gap between the
  norm of the global model's change after the first (third) mega-batch in
  the program and in the reference, over the reference's norm of that leaf
  or of the median leaf, whichever is larger.

Which leaves count, by rules on the reference alone: a leaf whose change
after the first mega-batch is under a thousandth of the median leaf's
moves by round-off alone; and a leaf whose change is under ten float32
epsilons of its own starting norm has moved by a few units in the last
place of its entries, where rounding each update decides the norm. Both
are left out of that number.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

NAMES = ("loss_gap", "update1_gap", "update3_gap")


EPS_F32 = 2.0**-23
RESOLVED_EPS = 10.0


def counted_leaves(ref_delta1: dict, ref_delta: dict, norms0: dict) -> list:
    med1 = float(np.median(list(ref_delta1.values())))
    return [k for k in ref_delta
            if ref_delta1[k] >= 1e-3 * med1
            and ref_delta[k] >= RESOLVED_EPS * EPS_F32 * norms0[k]]


def leaf_gap(prog: dict, ref: dict, leaves: list) -> float:
    med = float(np.median([ref[k] for k in leaves]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def readings(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` each hold ``losses`` (one per checked
    mega-batch) and ``deltas`` ({1: {leaf: norm}, 3: {leaf: norm}}); ``ref``
    also holds ``norms0`` ({leaf: norm at the start})."""
    d1 = ref["deltas"][1]
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"]))
    out = {"loss_gap": loss}
    for m in (1, 3):
        leaves = counted_leaves(d1, ref["deltas"][m], ref["norms0"])
        out[f"update{m}_gap"] = leaf_gap(prog["deltas"][m], ref["deltas"][m], leaves)
    return {k: (v if math.isfinite(v) else float("inf")) for k, v in out.items()}


def load_limits(chipbench_dir: str, workload: str) -> dict:
    """``limits/<workload>.json``: {number: {"limit": x, ...}}. Numbers
    without an entry are not compared."""
    path = os.path.join(chipbench_dir, "limits", f"{workload}.json")
    with open(path) as f:
        return {k: float(v["limit"]) for k, v in json.load(f).items()}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    compared = {k: {"value": values[k], "limit": limits[k]}
                for k in NAMES if k in limits}
    ok = bool(compared) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values()
    )
    return ok, compared
