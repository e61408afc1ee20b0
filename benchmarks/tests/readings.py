"""The readings a cell's limits are set from, taken on the chip at the cell's
own size. Run by hand (chip tool), never by the benchmark's own runs:

    python3 benchmarks/tests/readings.py --workload <cell> --seeds 11,12,13 [--seconds 10] [--out file.jsonl]

For each seed, in ONE process: a whole run of the cell (the program's numbers:
the LOWER readings), then, from the same data, the same trees and the same
score before the steady tree,
  control   the reference put in the program's place with every gradient and
            hessian rounded to bfloat16 before it is summed: the nearest
            precision below the float32 the configuration states
  half      the reference with half of every row chunk left out of the sums,
            leaf values taken from the rest
  stale     a step that returns its state unchanged: the second tree's sums
            taken at the first tree's scores (reads about 1 by construction;
            printed to show it)
each compared with the float64 reference and judged by the cell's limits
exactly as a run is: the UPPER readings, and ``correct`` for each, which has
to read false. One JSON line per seed.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402


def in_place(program, out):
    """A reference result dressed as what a program hands to the comparison:
    the valued trees carry ``out``'s leaf values, gains and counts; its
    resident score is its own walk (score_gap reads 0)."""
    dressed = []
    for t, tree in enumerate(program["valued"]):
        d = dict(tree)
        d.update(leaf_value=out["leaf_value"][t], split_gain=out["gain"][t],
                 leaf_count=out["leaf_count"][t], internal_count=out["node_count"][t])
        dressed.append(d)
    return dict(program, valued=dressed, step_scores=out["sample_score"],
                root_split_loss=0.0, node_split_loss=0.0,
                predict_followed=out["sample_score"][program["followed"] - 1],
                final_score=program["walk_all"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    sys.path.insert(0, harness.ROOT)
    from lib import compare, reference
    base = harness.resolve_cell(args.workload)
    device = harness.require_devices(int(base["cell"]["chips"]))
    job = harness.load_job(base)
    for seed in [int(s) for s in args.seeds.split(",")]:
        run = job.run(harness.make_ctx(base, seed=seed, seconds=args.seconds, trace=False,
                                       device=device))
        st, cfg = run["state"], base["config"]
        limits = compare.load_limits(harness.HERE, args.workload)
        ys = st["y"][st["sample"]]
        valued = st["program"]["valued"]
        line = {"workload": args.workload, "seed": seed, "correct": run["correct"],
                "program": run["info"]["numbers"], "end_to_end": run["end_to_end"]}
        for name, mode in (("control_bf16", {"precision": "bf16"}),
                           ("fault_half", {"rows_kept": 0.5})):
            out = job.refer(cfg, base["traffic"], st["X"], st["y"], valued,
                            st["score_before"], st["sample"], seed, **mode)
            nums = compare.numbers(in_place(st["program"], out), st["ref"], ys)
            correct, compared = compare.judge(nums, limits)
            line[name] = dict(nums, correct=correct,
                              over=[k for k, c in compared.items() if not c["ok"]])
        # a stale state: tree 2 grown and valued at tree 1's scores
        sem = cfg["semantics"]
        stale = reference.follow(st["X"], st["y"], [valued[1]], sample=st["sample"],
                                 learning_rate=float(sem["learning_rate"]),
                                 lambda_l2=float(sem["lambda_l2"]),
                                 init_score=float(sem["init_score"]))
        line["fault_stale_leaf_gap_max"] = compare.gaps(
            stale["leaf_value"][0], st["ref"]["leaf_value"][1])[0]
        # a finder that took the runner-up feature's best split
        line["fault_runner_up_root_split_loss"] = run["info"]["runner_up_feature_loss"]
        line["fault_runner_up_node_split_loss"] = run["info"]["runner_up_node_loss"]
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
        del run, st
    return 0


if __name__ == "__main__":
    sys.exit(main())
