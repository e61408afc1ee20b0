"""The comparison that decides ``correct`` for a training cell: what the
timed path produced (its trees, its resident scores, its predictions)
against the plain reference (lib/reference.py).

Every number is a gap that is 0 for a perfect program. A number is HELD when
the cell's limits file gives it a limit; the others are printed beside it as
information. ``correct`` is: every held number at or under its limit.

  count_mismatch   nodes and leaves of the valued trees whose row count
                   differs from the reference's routing by raw value (device
                   binning, routing, partition). Exact: limit 0.
  leaf_gap_max     worst leaf of the valued trees: |value - ref| over
  leaf_gap_med     max(|ref|, median |ref| of that tree); and the median leaf
  gain_gap_max     the same for split gains, per internal node (histogram
  gain_gap_med     sums and the split finder's arithmetic)
  loss_gap         worst of the valued steps: |logloss of the program's
                   resident scores - reference's| / reference's, sample rows
  pred_gap         Booster.predict (device forest walk) of the followed
                   trees against the reference's own scores, sample rows:
                   max |diff| over the RMS of the reference's score change
  root_split_loss  the split finder's choice: on the sample rows, the share of
                   the best root split's gain (exact scan of every feature at
                   every threshold, reference's gradients) that the tree's own
                   root split gives away; worst of the followed trees
  node_split_loss  the same at internal nodes drawn from the seed, over ALL
                   rows under the node; worst node of the valued trees
  score_gap        the resident training score after the LAST tree of the run
                   against the trees' own leaf values walked by raw value,
                   sample rows, same scale: every tree of the run counts

The VALUED trees are the first ``followed`` ones (set-up's dispatches, valued
from the initial score on) and the steady tree: one more dispatch made after
the window has closed, valued from the program's resident score before it.
``leaf_gap_steady`` and ``gain_gap_steady`` print the steady tree's share of
the two worst gaps.
"""
import json
import os

import numpy as np

from . import reference


def load_limits(here: str, cell: str) -> dict:
    """``benchmarks/limits/<cell>.json``: {"limits": {number: limit}, and the
    readings each limit was set from}. A cell without the file has no held
    number and cannot be correct."""
    with open(os.path.join(here, "limits", cell + ".json")) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def gaps(prog: np.ndarray, ref: np.ndarray):
    """(worst, median) of |prog - ref| over max(|ref|, the median |ref|)."""
    floor = float(np.median(np.abs(ref)))
    gap = np.abs(prog - ref) / np.maximum(np.abs(ref), floor)
    return float(gap.max()), float(np.median(gap))


def numbers(program: dict, ref: dict, y_sample: np.ndarray) -> dict:
    """``program``: valued (the trees the reference valued, as dicts: the
    followed ones, then the steady tree), followed (how many of them
    predict_followed covers), step_scores (the resident score at the sample
    rows after each valued step), predict_followed, final_score, walk_all
    (reference walk of all trees with the program's leaf values),
    root_split_loss, node_split_loss. ``ref``: what reference.follow returned
    for the valued trees."""
    k = int(program["followed"])
    mismatch = 0
    leaf_max = leaf_med = gain_max = gain_med = loss_gap = 0.0
    leaf_steady = gain_steady = 0.0
    for t, tree in enumerate(program["valued"]):
        L = int(tree["num_leaves"])
        mismatch += int(np.sum(np.asarray(tree["leaf_count"][:L]) != ref["leaf_count"][t]))
        mismatch += int(np.sum(np.asarray(tree["internal_count"][:L - 1])
                               != ref["node_count"][t]))
        leaf = gaps(np.asarray(tree["leaf_value"][:L], np.float64), ref["leaf_value"][t])
        gain = gaps(np.asarray(tree["split_gain"][:L - 1], np.float64), ref["gain"][t])
        leaf_max, leaf_med = max(leaf_max, leaf[0]), max(leaf_med, leaf[1])
        gain_max, gain_med = max(gain_max, gain[0]), max(gain_med, gain[1])
        if t >= k:
            leaf_steady, gain_steady = max(leaf_steady, leaf[0]), max(gain_steady, gain[0])
        lr_ = reference.logloss(ref["sample_score"][t], y_sample)
        lp = reference.logloss(np.asarray(program["step_scores"][t], np.float64), y_sample)
        loss_gap = max(loss_gap, abs(lp - lr_) / lr_)
    ref_last = ref["sample_score"][k - 1]
    scale = float(np.sqrt(np.mean((ref_last - program["init_score"]) ** 2)))
    pred_gap = float(np.max(np.abs(program["predict_followed"] - ref_last))) / scale
    score_gap = float(np.max(np.abs(program["final_score"] - program["walk_all"]))) / scale
    return {"count_mismatch": float(mismatch), "leaf_gap_max": leaf_max,
            "leaf_gap_med": leaf_med, "gain_gap_max": gain_max,
            "gain_gap_med": gain_med, "loss_gap": loss_gap,
            "pred_gap": pred_gap, "score_gap": score_gap,
            "root_split_loss": float(program["root_split_loss"]),
            "node_split_loss": float(program["node_split_loss"]),
            "leaf_gap_steady": leaf_steady, "gain_gap_steady": gain_steady}


def judge(nums: dict, limits: dict):
    """(correct, compared): ``compared`` maps each held number to
    {"value", "limit"}; a number with no limit is left out of it."""
    compared = {}
    for name, limit in limits.items():
        value = nums.get(name)
        # a number that could not be computed (nan) fails
        ok = value is not None and value == value and value <= limit
        compared[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    correct = bool(compared) and all(c["ok"] for c in compared.values())
    return correct, compared
