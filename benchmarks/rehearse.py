"""Rehearsals that cost no chip time. Run by hand; nothing imports this.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py tiny <cell> [--rows N] [--leaves L] [--seconds S] [--trace 1]
        the whole of a run (set-up, window, reference, comparison, result
        line) on the CPU at a tiny size. Its numbers are counts and
        correctness, never a device metric.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py compile <cell> [--rows N]
        compile the cell's training step for a DESCRIBED v5e (no chip) at the
        real shape, and print compile seconds and memory_analysis().
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402


CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}   # kind: for the peaks table


def tiny(args) -> int:
    ctx = harness.resolve_cell(args.workload)
    ctx["config"]["data"]["rows"] = args.rows
    ctx["config"]["data"]["block_rows"] = min(ctx["config"]["data"]["block_rows"], 4096)
    ctx["config"]["params"].update(num_leaves=args.leaves, device="cpu", verbose=0)
    ctx["traffic"]["sample_rows"] = min(ctx["traffic"]["sample_rows"], args.rows // 4)
    ctx["traffic"]["scan"]["rows"] = [args.rows // 50, args.rows]
    sys.path.insert(0, harness.ROOT)
    ctx = harness.make_ctx(ctx, seed=args.seed, seconds=args.seconds, trace=False, device=CPU)
    job = harness.load_job(ctx)
    job.memory_peak = lambda: 0       # the CPU keeps no peak_bytes_in_use
    run = job.run(ctx)
    print(json.dumps({"REHEARSAL_ON_CPU": True, "correct": run["correct"],
                      "compared": run["compared"], "numbers": run["info"]["numbers"],
                      "leaves": run["info"]["leaves"]}, indent=1))
    return 0 if run["correct"] else 1


def compile_described(args) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    sys.path.insert(0, harness.ROOT)
    import lightgbm_tpu as lgb
    from lib import datagen

    ctx = harness.resolve_cell(args.workload)
    cfg = ctx["config"]
    rows = args.rows or int(cfg["data"]["rows"])
    # a small real booster gives the step function and the pytree of its
    # arguments; the row dimension is then swapped for the real one
    small = 65536
    X, y = datagen.generate(dict(cfg["data"], block_rows=4096), small, 1)
    params = dict(cfg["params"], device="cpu", verbose=0)
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y))
    g = bst._gbdt
    chunk = int(g.spec.chunk_rows)
    rows_padded = -(-rows // chunk) * chunk
    fn = g._make_step(donate_override=(2, 3))
    consts, valid_Xb, valid_scores = g._dispatch_prep(g._step_shrinkage())
    live = (consts, valid_Xb, g.score, valid_scores, g.bag_mask, g._rng_key,
            g._iter_dev, g._shrink_cache[1])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    npad_small = int(g.num_data_padded)

    def shape_of(x):
        x = jnp.asarray(x) if not hasattr(x, "shape") else x
        shape = tuple(rows_padded if d == npad_small else d for d in x.shape)
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=one_chip)

    shapes = jax.tree.map(shape_of, live)
    print(f"compiling the step for a described v5e chip at {rows_padded} padded rows "
          f"x {g.spec.num_features} features, max_bin={cfg['params']['max_bin']} ...",
          flush=True)
    t = time.time()
    compiled = fn.lower(*shapes).compile()
    print(f"compile seconds (this sandbox's CPU cores, not the chip's host): "
          f"{time.time() - t:.1f}")
    ma = compiled.memory_analysis()
    print("memory_analysis:", ma)
    for k in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes"):
        print(f"  {k} = {getattr(ma, k) / 2**30:.3f} GiB")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("tiny", "compile"))
    ap.add_argument("workload")
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--leaves", type=int, default=15)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=3000000019)
    args = ap.parse_args()
    if args.mode == "tiny":
        args.rows = args.rows or 40000
        return tiny(args)
    return compile_described(args)


if __name__ == "__main__":
    sys.exit(main())
