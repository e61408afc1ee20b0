"""Run one cell of the benchmark once, in this process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration is the file that entry names; its traffic is
``benchmarks/traffic/<traffic>.json``, whose ``job`` selects the driver loop
``benchmarks/jobs/<job>.py``; with ``--trace 1`` each per-layer metric the
cell reports is read by ``benchmarks/metrics/<metric>.py``. Nothing here
names a cell, a configuration or a metric: adding one adds files and an entry
(benchmarks/README.md).

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced), and last of all ``compared``: each number the
correctness comparison held, beside its limit. The same numbers are the last
lines of standard error. Without a TPU, with fewer chips than the cell asks
for, or without the program beside it, the process exits non-zero and prints
no result.
"""
import time

T0 = time.time()          # set-up is counted from here: before any heavy import

import argparse            # noqa: E402
import importlib.util      # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import sys                 # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def log(msg: str) -> None:
    print(f"[{time.time() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def die(msg: str, code: int = 2):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve_cell(workload: str) -> dict:
    """Everything the cell's name leads to, from BENCHMARK.json down."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        die(f"no workload {workload!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    def reported(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {"cell": cell, "config": load_json(os.path.join(ROOT, entry["file"])),
            "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
            "per_layer": [m for m in bench["per_layer"] if reported(m)]}


def make_ctx(cell_ctx: dict, *, seed: int, seconds: float, trace: bool, device: dict,
             log=log, t0: float = None) -> dict:
    """What a job's ``run`` gets: the resolved cell plus this run's arguments.
    Set-up counts from ``t0``: now, unless the caller started earlier."""
    return dict(cell_ctx, seed=seed, seconds=seconds, trace=trace,
                t0=time.time() if t0 is None else t0,
                log=log, root=ROOT, here=HERE, device=device)


def load_job(cell_ctx: dict):
    """The driver loop the cell's traffic names: ``benchmarks/jobs/<job>.py``."""
    job = cell_ctx["traffic"]["job"]
    return load_module(os.path.join(HERE, "jobs", job + ".py"), "job_" + job)


def require_devices(chips: int) -> dict:
    """The device as jax reports it; no TPU or too few chips is an error."""
    import jax
    if jax.default_backend() != "tpu":
        die(f"jax found no accelerator (default backend "
            f"{jax.default_backend()!r}): a cell runs on a TPU or not at all", 3)
    devices = jax.devices()
    if len(devices) < chips:
        die(f"the cell asks for {chips} chip(s), jax sees {len(devices)}", 3)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def read_layer_metrics(ctx: dict, run: dict) -> dict:
    """Each per-layer metric through its own reader; a reader that finds
    nothing to read returns None and the metric is left out of the line."""
    out = {}
    for m in ctx["per_layer"]:
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        reader = load_module(path, "metric_" + m["name"].replace(".", "_").replace("-", "_"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    ctx = resolve_cell(args.workload)
    if not os.path.isdir(os.path.join(ROOT, "lightgbm_tpu")):
        die("the program (lightgbm_tpu/) is not beside BENCHMARK.json: "
            "nothing to measure")
    sys.path.insert(0, ROOT)
    device = require_devices(int(ctx["cell"]["chips"]))
    ctx = make_ctx(ctx, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                   device=device, t0=T0)   # set-up counts from the start of the process
    run = load_job(ctx).run(ctx)

    device = dict(device, memory_peak_bytes=int(run["memory_peak_bytes"]))
    result = {"correct": bool(run["correct"]), "attempted": int(run["attempted"]),
              "failed": int(run["failed"])}
    if args.trace:
        result["metrics"] = read_layer_metrics(ctx, run)
        device.update(busy_s=run["trace"]["busy_s"], window_s=run["trace"]["window_s"])
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in run["trace"]["ops"][:10]],
            "idle_gaps": [[k, v] for k, v in run["trace"]["gaps"][:10]]}
    else:
        units = {m["name"]: m["unit"] for m in ctx["end_to_end"]}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in run["end_to_end"].items() if k in units}
    result["device"] = device
    result["info"] = run.get("info", {})
    result["compared"] = run["compared"]
    for name, c in run["compared"].items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'OVER'}", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
