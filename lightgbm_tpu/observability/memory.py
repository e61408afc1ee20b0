"""HBM accounting: device memory stats + analytic pre-flight budget.

Two halves:

- ``device_memory()`` — the one home of the ``device.memory_stats()`` read
  (bench.py used to inline it twice), with backend fallbacks: TPU runtimes
  report ``bytes_in_use``/``peak_bytes_in_use``/``bytes_limit``, the CPU
  backend returns ``None``, and a jax-free process gets ``{}`` — callers
  never branch on backend. Folded into ``observability.snapshot()``.

- ``hbm_preflight(gbdt)`` — an analytic model of the wave loop's device
  residency as a function of N/features/bins/slots/wave state: the binned
  code matrix, packed gather rows, scores + gradients, the carried leaf
  partition, the per-leaf histogram cache, and the per-wave matmul
  temporaries. This is the "will it fit?" answer *before* the first
  compile — the prerequisite question for out-of-core training (ROADMAP
  item 3, arXiv 2005.09148: chunk residency planning needs exactly this
  breakdown) and for sizing double-buffered feeding (arXiv 1806.11248).
  ``engine.train`` logs the budget line and warns when the estimate
  exceeds the device capacity ``device_memory()`` reports. The estimate is
  cross-checked against the compiled step's ``memory_analysis()`` in
  tests/test_costs.py (tolerance-banded, two shape classes).

Pure host arithmetic — nothing here touches device state beyond the
(optional) ``memory_stats()`` query.
"""
from __future__ import annotations

import sys
from typing import Dict, Optional

_GB = float(1 << 30)


# ---------------------------------------------------------- device memory

def _backend_initialized() -> bool:
    """True iff some jax backend has ALREADY been instantiated — the single
    probe point for the private registry (same stance as
    parallel.comm.distributed_client). ``jax.local_devices()`` on a
    merely-imported jax would itself initialize the backend, which on a TPU
    host grabs the libtpu runtime exclusively."""
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:                                        # noqa: BLE001
        return False


def device_memory(device=None) -> Dict:
    """Memory stats of one device (default: first local), normalized across
    backends. Keys always present when a device exists: ``platform``;
    ``peak_bytes`` falls back peak_bytes_in_use -> bytes_in_use -> None and
    ``capacity_bytes`` is ``bytes_limit`` or None (CPU backends report
    nothing). Returns ``{}`` in a jax-free / backend-less process — the
    serving ``snapshot()`` path must never force a backend init, so with no
    explicit ``device`` the query runs only when a backend already
    exists."""
    if device is None and not _backend_initialized():
        return {}
    try:
        import jax
        dev = device if device is not None else jax.local_devices()[0]
    except Exception:                                        # noqa: BLE001
        return {}
    out: Dict = {"platform": getattr(dev, "platform", "unknown")}
    try:
        stats = dev.memory_stats() or {}
    except Exception:                                        # noqa: BLE001
        stats = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_alloc_size"):
        if stats.get(key) is not None:
            out[key] = int(stats[key])
    out["peak_bytes"] = out.get("peak_bytes_in_use",
                                out.get("bytes_in_use"))
    out["capacity_bytes"] = out.get("bytes_limit")
    return out


# ------------------------------------------------------ analytic pre-flight

def hbm_budget_bytes(config=None) -> Optional[int]:
    """The per-device HBM budget the residency decision judges against:
    env ``LGBM_TPU_HBM_BUDGET`` > config ``tpu_hbm_budget_bytes`` > the
    capacity the backend reports (None when nothing is known — CPU
    backends report no limit). The artificial knobs exist so out-of-core
    behavior is testable on any host (bench.py --stream trains a dataset
    >= 4x a configured budget on CPU)."""
    import os
    env = os.environ.get("LGBM_TPU_HBM_BUDGET", "")
    if env:
        try:
            return int(float(env))
        except ValueError:
            from ..utils.log import Log
            Log.warning("LGBM_TPU_HBM_BUDGET=%r is not a byte count — "
                        "ignoring it (use plain bytes, e.g. 17179869184)",
                        env)
    if config is not None and getattr(config, "tpu_hbm_budget_bytes", 0) > 0:
        return int(config.tpu_hbm_budget_bytes)
    cap = device_memory().get("capacity_bytes")
    return int(cap) if cap else None

def estimate_wave_residency(*, rows: int, cols: int, code_itemsize: int,
                            num_models: int, num_leaves: int,
                            hist_cols: int, hist_bins: int,
                            cache_cols: int, cache_bins: int,
                            num_bins_padded: int, slots: int,
                            chunk_rows: int, channels: int,
                            channel_bytes: int, packed_row_bytes: int = 0,
                            row_compact: bool = True, bagging: bool = False,
                            has_weight: bool = False, tree_batch: int = 1,
                            compensated: bool = False,
                            valid_bytes: int = 0,
                            stream_shard_bytes: int = 0,
                            linear_max_features: int = 0,
                            objective_bytes: int = 0) -> Dict:
    """Per-device HBM residency of one training step, by component (bytes).

    ``rows``/``cols`` are the PADDED per-device dims the step actually
    dispatches ([Npad(/D), cols_pad]); the model mirrors the buffers the
    grower documents (GrowState carry + the jit-level donated carry):

    - codes:      the binned (possibly bundled) code matrix — or, with
                  ``stream_shard_bytes`` set (tpu_residency=stream), the
                  TWO ping-pong shard buffers of the prefetcher: per-shard
                  instead of full-N residency is the whole point of the
                  out-of-core mode
    - metadata:   label/pad_mask(/bag_mask/weight) row vectors, f32
    - scores:     the [K, N] carried score (donation keeps ONE copy live)
    - gradients:  g and h, [K, N] f32 each
    - partition:  leaf_id, and with row_compact one more int32 a row (the
                  row's slot in the next wave)
    - packed:     the per-tree packed gather rows (code bytes + weight
                  channel bytes per row)
    - hist_cache: the [L+1, F_cache, B_cache, 3] f32 per-leaf cache
    - wave_temps: the [chunk, S*ch] rhs and the [F, B, S*ch] f32 accumulator
                  (x2 Kahan-compensated). NOT the [chunk, F, B] one-hot
                  operand: the TPU compiler fuses its producer into the
                  matmul and allocates none of it (0.68 GiB of temporaries
                  at 2,000 columns whatever the chunk, where the operand
                  would be 33.6 GB; PERF.md, PR 30)
    - trees:      stacked per-batch tree outputs (small)
    - valid:      attached validation sets (codes + scores), if any
    - linear:     linear_tree=true only (``linear_max_features`` > 0): the
                  device-resident raw f32 slice + missing plane
                  ([N, F] x 5 B), the per-leaf moment buffers
                  ([L+1, K+1, K+1] + [L+1, K+1] f32), and the chunked
                  one-hot gather intermediate of the fit leg
    - objective:  what the objective holds and its gradients pass uses
                  (``Objective.device_bytes``): 0 for an elementwise
                  objective; lambdarank's per-class doc index and mask, its
                  row map, the classes' gathered slots and the largest pair
                  block's temporaries
    """
    f32 = 4
    comp = {}
    comp["codes"] = (2 * stream_shard_bytes if stream_shard_bytes
                     else rows * cols * code_itemsize)
    comp["metadata"] = rows * f32 * (2 + int(bagging) + int(has_weight))
    comp["scores"] = num_models * rows * f32
    comp["gradients"] = 2 * num_models * rows * f32
    comp["partition"] = rows * f32 * (2 if row_compact else 1)
    comp["packed"] = rows * packed_row_bytes if row_compact else 0
    comp["hist_cache"] = (num_leaves + 1) * cache_cols * cache_bins * 3 * f32
    acc = hist_cols * hist_bins * slots * channels * f32
    comp["wave_temps"] = (acc * (2 if compensated else 1)
                          + chunk_rows * slots * channels * channel_bytes)
    per_tree = ((num_leaves) * num_bins_padded          # cat_mask, bool
                + 13 * (num_leaves + 1) * f32)          # node/leaf arrays
    comp["trees"] = max(1, tree_batch) * num_models * per_tree
    comp["valid"] = valid_bytes
    comp["linear"] = 0
    if linear_max_features > 0:
        K1 = linear_max_features + 1
        lin_chunk = min(chunk_rows, 8192)
        comp["linear"] = (
            rows * cols * (f32 + 1)                    # raw slice + missing
            + (num_leaves + 1) * (K1 * K1 + K1 + 1) * f32   # moments
            + lin_chunk * linear_max_features * cols * f32  # one-hot gather
            + lin_chunk * (K1 * K1 + K1 + 1) * f32)         # channel matrix
    comp["objective"] = objective_bytes
    total = int(sum(comp.values()))
    return {"components": {k: int(v) for k, v in comp.items()},
            "total_bytes": total,
            "total_gb": round(total / _GB, 3)}


def hbm_preflight(gbdt) -> Dict:
    """Analytic pre-flight for a constructed booster: reads the spec and
    array shapes the step will dispatch (no device traffic) and returns the
    ``estimate_wave_residency`` breakdown plus the dims it used. Results
    land in the registry as ``memory.preflight.*`` gauges."""
    import numpy as np

    spec = gbdt.spec
    pctx = gbdt.pctx
    # per-device rows under row-sharded strategies; feature-parallel
    # replicates rows but slices columns
    n_dev = max(1, pctx.num_devices)
    rows = gbdt.num_data_padded
    residency = getattr(gbdt, "residency", "device")
    stream_store = getattr(gbdt, "_stream_store", None)
    if stream_store is not None:
        # out-of-core: the code matrix never materializes on device — only
        # the prefetcher's two shard buffers count (per-shard residency)
        cols = int(stream_store.num_cols)
        code_itemsize = int(np.dtype(stream_store.dtype).itemsize)
        stream_shard_bytes = int(stream_store.shard_bytes) // n_dev \
            if pctx.mesh is not None and pctx.strategy in ("data", "voting") \
            else int(stream_store.shard_bytes)
    else:
        cols = int(gbdt.Xb.shape[1])
        code_itemsize = int(np.dtype(gbdt.Xb.dtype).itemsize)
        stream_shard_bytes = 0
    if pctx.mesh is not None and pctx.strategy in ("data", "voting"):
        rows = rows // n_dev
    hist_cols = cols
    if pctx.mesh is not None and pctx.strategy == "feature":
        hist_cols = max(1, cols // n_dev)
    B = spec.num_bins_padded
    B_hist = spec.hist_bins or B
    cache_cols = hist_cols
    try:
        cache_cols = int(gbdt.comm.reduced_hist_features(hist_cols))
    except Exception as e:                                   # noqa: BLE001
        from ..utils.log import Log
        Log.debug("hbm_preflight: reduced_hist_features unavailable "
                  "(using %d): %s: %s", cache_cols, type(e).__name__, e)
    channels, channel_bytes = (3, 4) if spec.hist_f64 else (5, 2)
    packed_row_bytes = 0
    if spec.row_compact:
        from ..ops.histogram import code_bytes_total, default_code_mode
        mode = spec.code_mode or default_code_mode(gbdt.Xb.dtype)
        packed_row_bytes = (code_bytes_total(hist_cols, mode)
                            + channels * channel_bytes)
    valid_bytes = 0
    for vs in getattr(gbdt, "valid_sets", ()):
        valid_bytes += int(vs.Xb.shape[0]) * (
            int(vs.Xb.shape[1]) * int(np.dtype(vs.Xb.dtype).itemsize)
            + gbdt.num_models * 4)
        if getattr(vs, "Xraw", None) is not None:
            # linear_tree: the valid raw slice (f32) + missing plane (bool)
            valid_bytes += int(vs.Xraw.shape[0]) * int(vs.Xraw.shape[1]) * 5
    dims = dict(rows=rows, cols=cols, code_itemsize=code_itemsize,
                num_models=gbdt.num_models, num_leaves=spec.num_leaves,
                hist_cols=hist_cols, hist_bins=B_hist,
                cache_cols=cache_cols, cache_bins=B_hist,
                num_bins_padded=B, slots=spec.hist_slots,
                chunk_rows=spec.chunk_rows, channels=channels,
                channel_bytes=channel_bytes,
                packed_row_bytes=packed_row_bytes,
                row_compact=spec.row_compact,
                bagging=bool(getattr(gbdt, "bagging_on", False)),
                has_weight=gbdt.weight is not None,
                tree_batch=int(getattr(gbdt, "tree_batch", 1)),
                compensated=spec.hist_f64, valid_bytes=valid_bytes,
                stream_shard_bytes=stream_shard_bytes,
                linear_max_features=(
                    int(getattr(gbdt.config, "linear_max_features", 0))
                    if getattr(gbdt, "linear_tree", False) else 0),
                objective_bytes=(gbdt.objective.device_bytes()
                                 if gbdt.objective is not None else 0))
    est = estimate_wave_residency(**dims)
    est["dims"] = dims
    est["residency"] = residency
    if stream_store is not None:
        est["stream"] = stream_store.describe()
    from . import get_registry
    reg = get_registry()
    reg.gauge("memory.preflight.total_bytes").set(est["total_bytes"])
    for k, v in est["components"].items():
        reg.gauge(f"memory.preflight.{k}_bytes").set(v)
    return est


def log_budget(estimate: Dict, devmem: Optional[Dict] = None,
               budget: Optional[int] = None) -> bool:
    """The engine.train budget line: one INFO line with the breakdown, and
    a WARNING when the estimate exceeds the budget (``tpu_hbm_budget_bytes``
    / env / reported device capacity). Returns True when the estimate fits
    (or no budget is known).

    Residency-aware: under ``tpu_residency=stream`` the estimate already
    counts only the two ping-pong shard buffers, the line says so, and the
    warning fires only when even the STREAMED state does not fit. Under
    forced device residency the warning points at ``tpu_residency=stream``
    as the remedy (auto-selection would already have taken it)."""
    from ..utils.log import Log

    comp = estimate["components"]
    top = sorted(comp.items(), key=lambda kv: -kv[1])[:4]
    detail = ", ".join(f"{k} {v / _GB:.2f}" for k, v in top if v)
    devmem = devmem if devmem is not None else device_memory()
    cap = budget if budget is not None else devmem.get("capacity_bytes")
    cap_s = f" / {cap / _GB:.2f} GB budget" if cap else ""
    residency = estimate.get("residency", "device")
    stream = estimate.get("stream")
    stream_s = ""
    if residency == "stream" and stream:
        stream_s = (f" [tpu_residency=stream: codes in {stream['n_shards']} "
                    f"host shards x {stream['shard_bytes'] / _GB:.3f} GB, "
                    f"{stream['code_mode']} packed]")
    Log.info("HBM pre-flight: %.2f GB estimated per device (%s)%s%s",
             estimate["total_bytes"] / _GB, detail, cap_s, stream_s)
    if cap and estimate["total_bytes"] > cap:
        if residency == "stream":
            Log.warning(
                "HBM pre-flight: even the STREAMED training state (%.2f "
                "GB — gradients/scores/partition + two shard buffers) "
                "exceeds the %.2f GB budget (platform=%s): shrink "
                "tpu_stream_shard_rows, shard rows across chips "
                "(tree_learner=data), or lower tree_batch",
                estimate["total_bytes"] / _GB, cap / _GB,
                devmem.get("platform"))
        else:
            Log.warning(
                "HBM pre-flight: estimated residency %.2f GB EXCEEDS the "
                "%.2f GB budget (platform=%s) — expect an OOM at first "
                "dispatch; set tpu_residency=stream (host-resident code "
                "shards, docs/TPU-Performance.md) or shard the rows "
                "across chips (tree_learner=data)",
                estimate["total_bytes"] / _GB, cap / _GB,
                devmem.get("platform"))
        return False
    return True
