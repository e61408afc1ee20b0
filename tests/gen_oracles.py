"""Regenerate tests/fixtures/oracles.json from the reference C++ engine.

The oracle constants used by tests/test_reference_parity.py and bench.py
(the ``reference_example_auc_oracle`` anchor) are REFERENCE-CLI outputs, not
hand-picked numbers. This script is their provenance: it rebuilds the
reference CLI (cmake + make from /root/reference, v2.0.10), re-runs the
exact workloads, parses the printed valid_1 metrics, and writes the fixture
with the config/data hashes of everything that determined each number — so
any drift in the bundled confs or data is caught as a hash mismatch rather
than a silently mismeasured anchor.

Run:  python tests/gen_oracles.py [--skip-build]

NOTE the reference CMakeLists pins EXECUTABLE_OUTPUT_PATH/LIBRARY_OUTPUT_PATH
to its own SOURCE tree (CMakeLists.txt:100-101) with plain SET(), which
cannot be overridden from the cache — the build briefly drops ``lightgbm`` /
``lib_lightgbm.so`` into /root/reference and this script immediately moves
them out again, leaving the tree untouched.
"""
import argparse
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

REF = "/root/reference"
BUILD = "/tmp/refbuild"
CLI = os.path.join(BUILD, "lightgbm_cli")
HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "fixtures", "oracles.json")

# (example dir, metric names to capture, extra CLI overrides)
EXAMPLE_RUNS = {
    "binary_classification": (["auc", "binary_logloss"],
                              ["max_bin=63", "num_trees=15"]),
    "regression": (["l2"], ["max_bin=63", "num_trees=15"]),
    "multiclass_classification": (["multi_logloss"],
                                  ["max_bin=63", "num_trees=15"]),
    "lambdarank": (["ndcg@5"], ["max_bin=63", "num_trees=15"]),
}
# bench.py's real-data quality anchor: the binary example's own train.conf
# driven to 100 iterations (metric=auc), nothing else overridden
BENCH_RUN = ("binary_classification", ["num_trees=100", "metric=auc"])


def sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for blk in iter(lambda: fh.read(1 << 20), b""):
            h.update(blk)
    return h.hexdigest()


def build_cli():
    os.makedirs(BUILD, exist_ok=True)
    subprocess.run(["cmake", REF, "-DCMAKE_BUILD_TYPE=Release"],
                   cwd=BUILD, check=True, capture_output=True)
    subprocess.run(["make", f"-j{os.cpu_count() or 4}", "lightgbm"],
                   cwd=BUILD, check=True, capture_output=True)
    # the reference pins its build outputs into the SOURCE tree — move them
    # straight out (the tree must stay pristine)
    shutil.move(os.path.join(REF, "lightgbm"), CLI)
    for stray in ("lib_lightgbm.so",):
        p = os.path.join(REF, stray)
        if os.path.exists(p):
            os.remove(p)


def run_case(example: str, overrides, want_iter: int):
    cwd = os.path.join(REF, "examples", example)
    out_model = os.path.join(BUILD, f"model_{example}.txt")
    cmd = [CLI, "config=train.conf", f"output_model={out_model}"] + overrides
    res = subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                         check=True)
    # [LightGBM] [Info] Iteration:15, valid_1 auc : 0.807646
    metrics = {}
    pat = re.compile(
        rf"Iteration:{want_iter},\s+valid_1\s+(\S+)\s*:\s*([-\d.eE]+)")
    for line in res.stdout.splitlines():
        m = pat.search(line)
        if m:
            metrics[m.group(1)] = float(m.group(2))
    if not metrics:
        sys.exit(f"no iteration-{want_iter} valid_1 metrics parsed from "
                 f"{example}:\n{res.stdout[-2000:]}")
    return metrics


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-build", action="store_true",
                    help="reuse an existing /tmp/refbuild/lightgbm_cli")
    args = ap.parse_args()
    if not args.skip_build or not os.path.exists(CLI):
        build_cli()

    out = {
        "_provenance": {
            "engine": "reference C++ CLI built from /root/reference "
                      "(bwilbertz/LightGBM v2.0.10), cmake Release",
            "generator": "tests/gen_oracles.py",
            "recipe": "cd examples/<ex>; lightgbm config=train.conf "
                      "<overrides>; parse 'Iteration:N, valid_1 <metric> : "
                      "<value>' from stdout",
        },
        "examples": {},
    }
    for example, (names, overrides) in EXAMPLE_RUNS.items():
        metrics = run_case(example, overrides, want_iter=15)
        cwd = os.path.join(REF, "examples", example)
        conf = os.path.join(cwd, "train.conf")
        data_files = sorted(
            f for f in os.listdir(cwd)
            if f.endswith((".train", ".test", ".query", ".weight")))
        out["examples"][example] = {
            "overrides": overrides,
            "iteration": 15,
            "metrics": {k: metrics[k] for k in names},
            "conf_sha256": sha256(conf),
            "data_sha256": {f: sha256(os.path.join(cwd, f))
                            for f in data_files},
        }
        print(example, {k: metrics[k] for k in names})

    example, overrides = BENCH_RUN
    metrics = run_case(example, overrides, want_iter=100)
    out["bench_reference_example"] = {
        "example": example,
        "overrides": overrides,
        "iteration": 100,
        "auc": metrics["auc"],
        "conf_sha256": sha256(os.path.join(REF, "examples", example,
                                           "train.conf")),
    }
    print("bench anchor:", metrics)

    with open(OUT, "w") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print("wrote", OUT)


if __name__ == "__main__":
    main()
