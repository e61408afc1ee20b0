"""The readings ``istella220-255-train``'s limits are set from, taken on the
chip at the cell's own size. Run by hand on one chip, never by the
benchmark's own runs:

    python3 benchmarks/tests/readings_rank.py --seeds 11,12,13 [--seconds 10] [--out file.jsonl]

For each seed, in ONE process: a whole run of the cell (the program's
numbers: the LOWER readings), then, from the same data, trees and score
before the steady tree, each of these in the program's place, compared with
the reference and judged by the cell's limits exactly as a run is (the UPPER
readings; ``correct`` has to read false for each):

  control_bf16   the reference's own gradients with the pair block computed
                 in bfloat16 (``reference_rank.DeviceGradients(precision=
                 "bf16")``): leaf values, gains and sample scores valued from
                 them, and their grad/hess at the sampled queries
  fault_half     the lambdas of every other query left out of the leaf sums
  fault_stale    the steady tree's score update lost: the resident score
                 after the run stands where it stood before the last tree
One JSON line per seed.
"""
import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402

CELL = "istella220-255-train"


def in_place(program, out, grads=None):
    """A reference result dressed as what a program hands to the comparison:
    the valued trees carry ``out``'s leaf values, gains and counts, its
    predictions are ``out``'s sample scores, its resident score its own walk
    (score_gap reads 0), and its grad/hess at the sampled queries ``grads``
    where given (else the program's)."""
    dressed = []
    for t, tree in enumerate(program["valued"]):
        d = dict(tree)
        d.update(leaf_value=out["leaf_value"][t], split_gain=out["gain"][t],
                 leaf_count=out["leaf_count"][t], internal_count=out["node_count"][t])
        dressed.append(d)
    return dict(program, valued=dressed, step_scores=out["sample_score"],
                predict_followed=out["sample_score"][program["followed"] - 1],
                final_score=program["walk_all"], grads=grads or program["grads"])


def half_queries(grads, sizes):
    """``grads`` with every other query's grad/hess set to 0."""
    qb = np.concatenate([[0], np.cumsum(sizes)])
    drop = np.zeros(int(qb[-1]), bool)
    for lo, hi in zip(qb[1::2], qb[2::2]):
        drop[lo:hi] = True

    def at(score):
        g, h = grads(score)
        return np.where(drop, 0.0, g), np.where(drop, 0.0, h)
    return at


def judge(nums, limits):
    from lib import compare
    correct, compared = compare.judge(nums, limits)
    return dict(nums, correct=correct, over=[k for k, c in compared.items() if not c["ok"]])


def readings(job, base, run, limits) -> dict:
    """The three upper readings of one run (its ``state``)."""
    from lib import reference, reference_rank
    st, cfg = run["state"], base["config"]
    sizes, label, sample, queries = st["sizes"], st["label"], st["sample"], st["queries"]
    valued = st["program"]["valued"]
    out = {}
    bf16 = reference_rank.DeviceGradients(sizes, label, precision="bf16",
                                          **job.semantics(cfg))
    ref_b = job.refer(cfg, sizes, label, st["X"], valued, st["score_before"], sample,
                      gradients=bf16)
    zeros = np.zeros(len(label))
    grads = {"tied": tuple(a[sample] for a in bf16(zeros)),
             "steady": tuple(a[sample] for a in bf16(st["score_before"]))}
    nums = job.numbers(in_place(st["program"], ref_b, grads), st["ref"], sizes, queries)
    out["control_bf16"] = judge(nums, limits)
    f32 = reference_rank.DeviceGradients(sizes, label, **job.semantics(cfg))
    ref_h = job.refer(cfg, sizes, label, st["X"], valued, st["score_before"], sample,
                      gradients=half_queries(f32, sizes))
    nums = job.numbers(in_place(st["program"], ref_h), st["ref"], sizes, queries)
    out["fault_half"] = judge(nums, limits)
    X_sample = st["X"][sample]
    stale = dict(st["program"], final_score=reference.walk(
        X_sample, st["trees"][:-1], float(cfg["semantics"]["init_score"])))
    nums = job.numbers(stale, st["ref"], sizes, queries)
    out["fault_stale"] = judge(nums, limits)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    sys.path.insert(0, harness.ROOT)
    from lib import compare
    base = harness.resolve_cell(CELL)
    device = harness.require_devices(int(base["cell"]["chips"]))
    job = harness.load_job(base)
    limits = compare.load_limits(harness.HERE, CELL)
    for seed in [int(s) for s in args.seeds.split(",")]:
        run = job.run(harness.make_ctx(base, seed=seed, seconds=args.seconds, trace=False,
                                       device=device))
        line = {"workload": CELL, "seed": seed, "correct": run["correct"],
                "program": run["info"]["numbers"], "end_to_end": run["end_to_end"],
                "leaves": run["info"]["leaves"]}
        line.update(readings(job, base, run, limits))
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
