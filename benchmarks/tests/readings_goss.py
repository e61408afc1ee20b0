"""The readings ``criteo67-255-goss-train``'s limits are set from, taken on
the chip at the cell's own size. Run by hand (chip tool), never by the
benchmark's own runs:

    python3 benchmarks/tests/readings_goss.py --seeds 11,12,13 [--seconds 10] [--out file.jsonl]

``tests/readings.py`` for a cell whose steady tree is sampled. For each seed,
in ONE process: a whole run of the cell (the program's numbers: the LOWER
readings), then, from the same data, the same trees, the same score before
the steady tree and the program's own mask of it, each of these in the
program's place, compared with the float64 reference and judged by the
cell's limits exactly as a run is (the UPPER readings; ``correct`` has to
read false for every one):
  control_bf16        every g and h rounded to bfloat16 before it is weighted
                      and summed: the nearest precision below the float32 the
                      configuration states
  fault_half          half of every row chunk left out of the sums
  fault_no_amplify    the drawn rows keep weight 1: GOSS without its
                      (N - top_k) / other_k
  fault_unscored      out-of-sample rows not scored by the steady tree
  fault_top_by_g      the top set taken by |g| alone (a mask drawn so)
  fault_half_sample   half of the sample dropped again (a mask drawn so)
One JSON line per seed.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402
from readings import in_place  # noqa: E402

CELL = "criteo67-255-goss-train"
VALUE_FAULTS = (("control_bf16", {"precision": "bf16"}),
                ("fault_half", {"rows_kept": 0.5}),
                ("fault_no_amplify", {"amplified": False}),
                ("fault_unscored", {"score_out_of_sample": False}))
SAMPLE_FAULTS = (("fault_top_by_g", {"top_by": "g"}),
                 ("fault_half_sample", {"kept": 0.5}))


def controls(job, cfg: dict, traffic: dict, run: dict, seed: int, limits: dict) -> dict:
    """{name: the numbers with the control or fault in the program's place,
    ``correct`` and the limits it is ``over``} for every control and fault."""
    from lib import compare, reference_goss
    st = run["state"]
    ys = st["y"][st["sample"]]
    program, sound = st["program"], st["ref"]
    out = {}
    for name, mode in VALUE_FAULTS:
        faulty = job.refer(cfg, traffic, st["X"], st["y"], program["valued"],
                           st["score_before"], st["sample"], seed,
                           included=st["included"], **mode)
        dressed = in_place(program, faulty)
        # what the steady tree left off the resident score, where it did
        dressed["final_score"] = program["walk_all"] - (
            sound["sample_score"][-1] - faulty["sample_score"][-1]
            if "score_out_of_sample" in mode else 0.0)
        nums = job.numbers(dressed, dict(sound, sample=faulty["sample"]), ys)
        out[name] = nums
    params = cfg["params"]
    g, h = reference_goss.gradients(st["score_before"], st["y"])
    for name, planted in SAMPLE_FAULTS:
        mask = reference_goss.draw_sample(
            st["score_before"], st["y"], top_rate=float(params["top_rate"]),
            other_rate=float(params["other_rate"]), seed=seed, **planted)
        read = reference_goss.read_sample(abs(g * h), mask, float(params["top_rate"]),
                                          float(params["other_rate"]))
        out[name] = dict(run["info"]["numbers"], top_missed=read["top_missed"],
                         other_count_gap=read["other_count_gap"])
    for name, nums in out.items():
        correct, compared = compare.judge(nums, limits)
        out[name] = dict(nums, correct=correct,
                         over=[k for k, c in compared.items() if not c["ok"]])
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
        run = job.run(harness.make_ctx(base, seed=seed, seconds=args.seconds,
                                       trace=False, device=device))
        line = {"workload": CELL, "seed": seed, "correct": run["correct"],
                "program": run["info"]["numbers"], "sample": run["info"]["sample"],
                "trees": run["info"]["trees"], "end_to_end": run["end_to_end"]}
        line.update(controls(job, base["config"], base["traffic"], run, seed, limits))
        line["fault_runner_up_root_split_loss"] = run["info"]["runner_up_feature_loss"]
        line["fault_runner_up_node_split_loss"] = run["info"]["runner_up_node_loss"]
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
