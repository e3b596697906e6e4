"""The comparison that decides ``correct``.

A run's first three rounds go through the program's own round call; the
plain reference replays them from the seed.  The numbers compared:

- ``loss_rel``: the largest relative gap of a round's mean client loss;
- ``acc_gap``, ``acc_mean``: the largest, and the mean over the rounds,
  of the gap of a round's mean personalised accuracy (the masked eval);
- ``update1_leaf``, ``update1_median``: after round 1, the server's update
  (the Eq. 13 mean the next round's clients receive), by the worst leaf
  and by the median leaf;
- ``state3_leaf``, ``state3_median``: after round 3, the clients' stored
  state as round 4 would gather it (each leaf's change of the
  personalised params over all clients, and of the stored local
  updates), by the worst leaf and by the median leaf;
- ``rounds_seen_diff``: clients whose stored round count differs, which
  is exact.

A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf.  Leaves whose
round-1 update in the reference is under a thousandth of the median
leaf's move by round-off alone and are left out of ``state3_leaf``.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("loss_rel", "acc_gap", "acc_mean", "update1_leaf", "update1_median", "state3_leaf",
           "state3_median", "rounds_seen_diff")
QUIET_LEAF = 1e-3


def gaps(prog: dict, ref: dict, keep=None) -> list:
    """Each leaf's |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    floor = float(np.median([ref[k] for k in ref]))
    return [abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in ref if keep is None or k in keep]


def leaf_gaps(prog: dict, ref: dict, n: int = 3):
    """The ``n`` worst leaves as (gap, leaf, program norm, reference norm),
    for the log."""
    floor = float(np.median(list(ref.values())))
    gaps = sorted(((abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30), k, prog[k], ref[k])
                   for k in ref), reverse=True)
    return gaps[:n]


def readings(prog: dict, ref: dict) -> dict:
    """Both sides as ``{"loss": [3], "acc": [3], "update1": {leaf: norm},
    "state3": {"params": {...}, "delta": {...}, "rounds_seen": (K,)}}``."""
    u_ref = ref["update1"]
    floor = float(np.median(list(u_ref.values())))
    moving = {k for k, v in u_ref.items() if v >= QUIET_LEAF * floor}
    acc = [abs(p - r) for p, r in zip(prog["acc"], ref["acc"])]
    update = gaps(prog["update1"], u_ref)
    state = [gaps(prog["state3"][part], ref["state3"][part], moving)
             for part in ("params", "delta")]
    return {
        "loss_rel": max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])),
        "acc_gap": max(acc),
        "acc_mean": float(np.mean(acc)),
        "update1_leaf": max(update),
        "update1_median": float(np.median(update)),
        "state3_leaf": max(max(g) for g in state),
        "state3_median": max(float(np.median(g)) for g in state),
        "rounds_seen_diff": int(np.sum(np.asarray(prog["state3"]["rounds_seen"])
                                       != np.asarray(ref["state3"]["rounds_seen"]))),
    }


def judge(numbers: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}): every number the cell's
    limits name at or under its limit, and finite.  A number the limits
    leave out is not compared (PERF.md says why, per cell)."""
    table = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS if k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return bool(ok), table
