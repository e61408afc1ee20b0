"""GBDT boosting driver — the reference's training loop, device-resident.

Reference: src/boosting/gbdt.{cpp,h}. The per-iteration pipeline
(gbdt.cpp:379-473) — Boosting() gradients -> Bagging -> per-class
tree_learner->Train -> Shrinkage -> UpdateScore — is compiled into ONE jitted
`step` whose tree growth runs a device-side while_loop (grower.py). The host
loop only enqueues steps and fetches scores at eval points; a host sync
drains the asynchronous dispatch queue, so nothing in the hot loop blocks.

Semantics kept from the reference:
- boost-from-average initial score folded into the first tree as a bias
  (gbdt.cpp:357-377 + AddBias :445-447),
- bagging re-sampled every `bagging_freq` iterations (gbdt.cpp:225-270;
  mask-based Bernoulli instead of exact-count index partition — OOB rows are
  excluded from histograms/counts but still routed so score updates stay
  O(N) gathers),
- per-tree feature_fraction sampling (serial_tree_learner.cpp:240-252),
- training stops when no tree in an iteration could split
  (gbdt.cpp:465-471), checked at sync points,
- early stopping on validation metrics (gbdt.cpp:493-518).
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import numpy as np

from .. import observability as obs
from ..observability import costs as obs_costs
from ..config import Config
from ..dataset import ConstructedDataset, Metadata, MetadataDuckTyping
from ..grower import (GrowerSpec, TreeArrays, WaveStats, counts_past_f32,
                      grow_tree, route_table_cols, scan_block_pairs,
                      scan_hist_shape, wave_totals)
from ..ops.histogram import (hist_pass_shape, num_channels,
                             resolve_compact_frac, table_lookup)
from ..parallel.comm import make_parallel_context, tree_collective_bytes
from ..metrics import Metric, create_metrics
from ..robustness import allowed_host_sync
from ..objectives import Objective, create_objective
from ..ops.predict import leaves_from_binned
from ..tree import Tree, tree_from_device_arrays
from ..utils.log import Log


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# one-time (per process) EFB-on-TPU throughput warning — the measured loss
# is per-workload, not per-booster, so repeating it per construction is noise
_EFB_TPU_WARNED = [False]


class ValidSet(MetadataDuckTyping):
    # the mixin supplies the duck-typed Dataset surface so user fevals
    # written against the reference python-package contract keep working
    def __init__(self, name: str, Xb_dev: jnp.ndarray, metadata: Metadata,
                 metrics: List[Metric], num_data: int):
        self.name = name
        self.Xb = Xb_dev
        self.metadata = metadata
        self.metrics = metrics
        self.num_data = num_data
        self.score: Optional[jnp.ndarray] = None
        # linear_tree=true only: device raw-feature slice (NaN-sanitized)
        # + missing plane for the valid-score linear epilogue
        self.Xraw: Optional[jnp.ndarray] = None
        self.Xmiss: Optional[jnp.ndarray] = None


from ..analysis.contracts.registry import trace_entry


class SampleStats(NamedTuple):
    """What a step's row sample held, counted on the device where the sample
    is drawn (int32 scalars; published per tree as ``sample.rows_top``,
    ``sample.rows_other``, ``sample.rows_in``)."""
    rows_top: jnp.ndarray      # rows kept for their gradient (GOSS's top set)
    rows_other: jnp.ndarray    # rows kept by a uniform draw (GOSS's other
                               # set; every row of a bag)
    rows_in: jnp.ndarray       # rows the tree is grown on

    @classmethod
    def count(cls, is_top, is_other, mask) -> "SampleStats":
        def rows(a):
            return jnp.sum((a > 0).astype(jnp.int32))
        return cls(rows(is_top), rows(is_other), rows(mask))


@trace_entry("train_step.fused")
class GrowRecord(NamedTuple):
    """What one iteration leaves on the device beside its trees, until the
    trees are fetched: the leaf counts (the no-splits check reads them) and
    the wave loop's own counters. One record so that a single output rides
    through ``step_body`` / the ``tree_batch`` scan / the nan-policy tuple;
    appended per iteration, popped on rollback, fetched lazily."""
    num_leaves: jnp.ndarray           # i32 [K]
    stats: Optional[WaveStats]        # leading axes [K, D]; None where the
                                      # loop did not run in this process's
                                      # step (checkpoint restore, streaming)
    sample: Optional[SampleStats] = None  # None where the step draws no row
                                      # sample: that program carries no
                                      # counter


def _abstract_signature(args) -> Dict[str, str]:
    """``{path: "dtype[shape] committed|uncommitted"}`` of a dispatch's
    arguments: what jit keys its trace cache on, as far as the host sees
    it. Read only when the step has just been traced."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(args)[0]:
        sig = f"{getattr(leaf, 'dtype', type(leaf).__name__)}" \
              f"{list(getattr(leaf, 'shape', ()))}"
        if hasattr(leaf, "committed"):
            sig += " committed" if leaf.committed else " uncommitted"
        if getattr(leaf, "weak_type", False):
            sig += " weak"
        out[jax.tree_util.keystr(path)] = sig
    return out


class GBDT:
    """Boosting driver (reference class GBDT, src/boosting/gbdt.h:25)."""

    average_output = False  # RF overrides (boosting.h average_output_)
    # fused multi-tree steps (tree_batch > 1) need every per-iteration hook
    # to be device-resident; DART/GOSS override to False and fall back to 1
    supports_tree_batch = True

    def __init__(self, config: Config, train_set: ConstructedDataset,
                 objective: Optional[Objective] = None):
        # one set-up span after another, no second unnamed: each
        # ``stage(name)`` in ``_build`` ends the stage before it
        # (docs/Observability.md, the tiled set-up tree)
        with obs.setup_stages() as stage:
            self._build(config, train_set, objective, stage)

    def _build(self, config: Config, train_set: ConstructedDataset,
               objective: Optional[Objective], stage) -> None:
        self.config = config
        self.train_set = train_set
        stage("booster.mesh")
        # multi-host wiring FIRST — jax.distributed.initialize must run
        # before anything touches the XLA backend (mirrors the reference's
        # Network::Init-before-LoadData ordering, application.cpp:167-178)
        from ..parallel.comm import init_distributed
        init_distributed(config)
        self.objective = objective if objective is not None else create_objective(config)
        self.num_models = self.objective.num_models if self.objective else max(config.num_class, 1)
        K = self.num_models

        # ---- device mesh / parallel strategy (reference Network::Init,
        #      application.cpp:167-178; tree_learner grid tree_learner.cpp:9).
        #      The training matrix shape rides along so tree_learner=auto can
        #      resolve the mesh axis (rows vs features) from the shape class
        #      (parallel/comm.py choose_tree_learner). --
        self.pctx = make_parallel_context(
            config, shape=(train_set.num_data, train_set.num_features))

        # ---- pre-partitioned data (reference dataset_loader.cpp:159-221 +
        #      Metadata::CheckOrPartition): under is_pre_partition each
        #      process loaded ONLY its own row shard, so the global row space
        #      is assembled as equal per-process blocks — the feature matrix
        #      stays process-local (the memory that matters at scale) while
        #      the cheap metadata (4-8 B/row) is gathered host-side so
        #      boost-from-average / objectives / metrics see global stats. --
        N = train_set.num_data
        meta_global = train_set.metadata
        self._block_counts: Optional[List[int]] = None
        if (config.is_pre_partition and self.pctx.multi_process
                and self.pctx.strategy in ("data", "voting")):
            from ..parallel.comm import host_allgather
            md = train_set.metadata
            blocks = host_allgather(
                dict(n=int(N), label=np.asarray(md.label, np.float32),
                     weight=None if md.weight is None
                     else np.asarray(md.weight, np.float32),
                     qsizes=None if md.query_boundaries is None
                     else np.diff(md.query_boundaries).astype(np.int64),
                     init_score=None if md.init_score is None
                     else np.asarray(md.init_score, np.float32)),
                "pre_partition_meta")
            self._block_counts = [int(b["n"]) for b in blocks]
            N = int(sum(self._block_counts))
            meta_global = Metadata(N)
            meta_global.set_label(np.concatenate([b["label"] for b in blocks]))

            def _all_or_none(key, what):
                have = sum(b[key] is not None for b in blocks)
                if have not in (0, len(blocks)):
                    Log.fatal("is_pre_partition: %d of %d shards have %s — "
                              "every shard must provide them or none",
                              have, len(blocks), what)
                return bool(have)

            if _all_or_none("weight", "weights"):
                meta_global.set_weight(
                    np.concatenate([b["weight"] for b in blocks]))
            # ranking: each shard holds WHOLE queries (the reference loads
            # full queries per machine and rebuilds query_boundaries from
            # the used-row set, metadata.cpp:97-127); the global query list
            # is the block-ordered concatenation of per-shard query sizes
            if _all_or_none("qsizes", "query/group data"):
                meta_global.set_group(
                    np.concatenate([b["qsizes"] for b in blocks]))
            if _all_or_none("init_score", "init_score"):
                # per-shard arrays are (k*n_b,) class-major with a common k
                k = max(len(blocks[0]["init_score"]) // max(blocks[0]["n"], 1),
                        1)
                if any(len(b["init_score"]) != k * b["n"] for b in blocks):
                    Log.fatal("is_pre_partition: init_score length must be "
                              "the same per-row multiple on every shard")
                meta_global.set_init_score(np.concatenate(
                    [b["init_score"].reshape(k, b["n"]) for b in blocks],
                    axis=1).reshape(-1))
            Log.info("pre-partitioned data: %d rows across %d processes %s",
                     N, len(blocks), self._block_counts)
        self._meta_global = meta_global

        stage("booster.objective")
        if self.objective is not None:
            self.objective.init(meta_global, N)

        stage("booster.shapes")
        F = train_set.num_features
        # feature padding: block-partitioned strategies need F % devices == 0
        F_pad = self.pctx.pad_features_to(max(F, 1))
        # row padding: per-device rows must be a chunk multiple; equal
        # per-process blocks under pre-partition (the largest shard sizes
        # every block so local data always fits its block)
        Drow = self.pctx.pad_rows_multiple()
        n_for_pad = N if self._block_counts is None else \
            max(self._block_counts) * len(self._block_counts)
        per_target = max((n_for_pad + Drow - 1) // Drow, 1)

        meta = train_set.feature_meta_arrays()
        num_leaves = config.max_leaves_by_depth
        Bpad = max(8, _round_up(train_set.max_num_bin, 8))

        # ---- EFB bundling (reference Dataset::Construct enable_bundle path,
        #      dataset.cpp:236-247): pack near-exclusive features into fewer
        #      histogram columns, for EVERY learner strategy — EFB precedes
        #      learner choice in the reference too (dataset.cpp:66-210).
        #      NATIVE default: bundle space is the representation end-to-end
        #      — the split scan runs on bundled bins directly
        #      (ops/split_finder.per_feature_best_bundled, the reference's
        #      FeatureGroup discipline), data-parallel reduce-scatters
        #      bundle-column blocks (DataParallelBundledComm), voting psums
        #      selected bundle columns, and row routing compares bundled
        #      codes against the split's bundle range. The legacy
        #      tpu_efb_unpack arm keeps the pre-redesign layout (unpack to
        #      [T, F, B, 3] before the scan; per-row decode in routing) as
        #      the A/B + parity pin.
        #      - feature-parallel: BUNDLES are the partitioned unit
        #        (FeatureParallelBundledComm — the reference partitions
        #        post-EFB feature groups the same way);
        #      - pre-partitioned: per-shard row samples are KV-allgathered so
        #        every rank plans from the IDENTICAL sample (the reference
        #        plans bundles from the same distributed sample it bins from,
        #        dataset_loader.cpp:820-899), then materializes its local
        #        shard against the common plan. ----
        stage("booster.efb")
        self.bundle = None
        bundle_plan = None
        # legacy unpack arm (tpu_efb_unpack). The one unsupported native
        # combination — voting + categorical (the PV-Tree phase-2
        # selected-column scan is numerical-only in bundle space,
        # parallel/comm.py scan_slot_b) — forces the legacy arm HERE,
        # before any engagement logging/warning reads the arm, rather
        # than silently dropping categorical candidates; the warning
        # fires below only if bundling actually engages
        self._efb_unpack = bool(config.tpu_efb_unpack)
        _efb_unpack_forced = False
        if (not self._efb_unpack and self.pctx.strategy == "voting"
                and bool(meta["is_categorical"].any())):
            self._efb_unpack = True
            _efb_unpack_forced = True
        from ..efb import (_SAMPLE_ROWS, no_pair_fits, plan_bundles,
                           sample_row_indices, sample_rows)
        efb_num_bins = meta["num_bins"].astype(np.int64)
        efb_default_bin = meta["default_bin"].astype(np.int64)
        # the bin rule first: where no two features can share a group by
        # their bins alone (fewer than two features included) there is no
        # plan, so no sample is drawn, binned or exchanged. It reads the
        # global bin mappers only, identical on every rank, so under
        # pre-partition every rank skips the host_allgather below together.
        efb_sample = None
        if (config.enable_bundle != "false"
                and not no_pair_fits(efb_num_bins, efb_default_bin)):
            if self._block_counts is not None:
                from ..parallel.comm import host_allgather
                per_rank = max(1, _SAMPLE_ROWS // len(self._block_counts))
                parts = host_allgather(
                    sample_rows(train_set.X_binned, per_rank), "efb_sample")
                efb_sample = np.concatenate(parts, axis=0)
            elif train_set.deferred:
                # deferred device ingest: plan from a host-binned row
                # SAMPLE (the plan is a pure function of the sample, and
                # bin_rows draws the exact rows sample_rows would) — the
                # full host bin matrix is only materialized below if the
                # plan actually wins
                efb_sample = train_set.bin_rows(sample_row_indices(N))
            else:
                efb_sample = sample_rows(train_set.X_binned)
        # rows of the planning sample binned or read; 0 where no sample was
        # drawn (the bin rule decided, or bundling is off)
        obs.get_registry().gauge("efb.sample_rows").set(
            0 if efb_sample is None else efb_sample.shape[0])
        if efb_sample is not None:
            plan = plan_bundles(
                None if train_set.deferred else train_set.X_binned,
                efb_num_bins, efb_default_bin, config,
                sample=efb_sample, num_data=N)
            if plan is not None:
                Bb_pad = max(8, _round_up(plan.max_bundle_bins, 8))
                # the BundlePlan win ratio: bundling wins when it shrinks
                # the one-hot matmul (G*Bb < F*B), OR when it at least
                # halves the column count without growing the matmul much
                # — the per-wave row gather and the HBM footprint scale
                # with raw column count, so a Bosch-shaped matrix (many
                # low-bin exclusive columns) wins even at equal matmul
                # width, EFB's "densifier" role for sparse data
                # (dataset.cpp:236-247, sparse_bin.hpp:68). With the
                # bundle-space scan the decode tax the round-5 bench
                # measured is gone, so this ratio IS the crossover:
                # enable_bundle=auto resolves per shape class the way
                # tpu_hist_kernel=auto does, enable_bundle=true engages
                # any plan regardless.
                shrinks_matmul = plan.num_groups * Bb_pad < 0.9 * F * Bpad
                shrinks_cols = (plan.num_groups * 2 <= F
                                and plan.num_groups * Bb_pad <= 1.25 * F * Bpad)
                wins = shrinks_matmul or shrinks_cols
                if config.enable_bundle == "auto":
                    Log.debug(
                        "enable_bundle=auto resolved to %s (%d features -> "
                        "%d bundles, matmul %d vs %d columns)",
                        "true" if wins else "false", F, plan.num_groups,
                        plan.num_groups * Bb_pad, F * Bpad)
                if wins or config.enable_bundle == "true":
                    bundle_plan = plan
                    if plan.X_bundled is None:
                        # the plan won under deferred ingest: bundling
                        # needs the host bin matrix after all — pay the
                        # host materialization now (device ingest serves
                        # the unbundled layout only)
                        from ..efb import materialize_bundles
                        plan.X_bundled = materialize_bundles(
                            plan, train_set.X_binned,
                            meta["default_bin"].astype(np.int64))
                    if _efb_unpack_forced:
                        Log.warning(
                            "tree_learner=voting with categorical features "
                            "keeps the legacy EFB unpack arm "
                            "(tpu_efb_unpack=true forced)")
                    Log.info("EFB: %d features bundled into %d columns "
                             "(%d max bundle bins), scan=%s", F,
                             plan.num_groups, plan.max_bundle_bins,
                             "unpack (legacy tpu_efb_unpack arm)"
                             if self._efb_unpack else "bundle-space")
                    if (self.pctx.devices[0].platform == "tpu"
                            and self._efb_unpack
                            and not _EFB_TPU_WARNED[0]):
                        # bundle-space split finding removed the decode
                        # gather from the default arm; only the legacy
                        # unpack arm still pays that layout
                        _EFB_TPU_WARNED[0] = True
                        Log.warning(
                            "tpu_efb_unpack=true on the TPU backend: the "
                            "legacy unpack arm pays a per-row bundle "
                            "decode gather that an earlier on-chip "
                            "session measured as a 3.5x throughput loss "
                            "on a Bosch-shaped run "
                            "(docs/TPU-Performance.md). It exists as the "
                            "A/B + parity arm; drop the knob for the "
                            "bundle-space default")

        # ---- histogram kernel shape (needs the FINAL column/bin layout,
        #      hence after EFB planning) ----
        stage("booster.layout")
        # auto slots: 25 x 5 bf16 channels = 125 matmul columns — one full
        # MXU tile (128) — while quartering the wave count at 255 leaves.
        # User-set slot counts clamp to the leaf budget: the wave loop's
        # top_k over [num_leaves+1] gains requires S <= num_leaves.
        slots = config.tpu_hist_slots or max(1, min(25, num_leaves - 1))
        slots = max(1, min(slots, num_leaves))
        # single source for the kernel shape (cols_pad / Bb_pad are REUSED
        # by the bundle materialization below — recomputing them there
        # risked the dispatched shape diverging from what was decided here)
        if bundle_plan is not None:
            G_raw = bundle_plan.X_bundled.shape[1]
            if self.pctx.strategy == "feature" or (
                    self.pctx.strategy == "data" and not self._efb_unpack):
                # bundle blocks are the partition unit (feature-parallel
                # always; data-parallel on the native arm, where the
                # psum_scatter runs over bundle blocks): G % devices == 0
                cols_pad = self.pctx.pad_features_to(G_raw)
            else:
                cols_pad = G_raw
        else:
            cols_pad = F_pad
        _kernel_dtype = (bundle_plan.X_bundled.dtype
                         if bundle_plan is not None
                         else train_set.code_dtype)
        _kernel_bins = Bb_pad if bundle_plan is not None else Bpad
        # rows a chunk of the histogram pass: a static function of the
        # shapes, with tpu_hist_chunk as its upper bound — today's 32,768
        # rows up to 256 columns at 256 bins, fewer for a wider table
        # (ops/histogram.hist_pass_shape). Feature-parallel devices
        # histogram their own column block only.
        _hist_cols = (cols_pad // self.pctx.num_devices
                      if self.pctx.strategy == "feature" else cols_pad)
        chunk, _shape_rule = hist_pass_shape(
            per_target, _hist_cols, _kernel_bins,
            4 if config.tpu_hist_f64 else 2, config.tpu_hist_chunk)
        # ---- residency (ROADMAP item 3, docs/TPU-Performance.md): decide
        #      BEFORE any device placement whether the binned code matrix
        #      is HBM-resident ("device") or streams from host shards
        #      ("stream", ops/stream.py). "auto" streams iff the analytic
        #      device-residency estimate exceeds the per-device HBM budget
        #      (tpu_hbm_budget_bytes / LGBM_TPU_HBM_BUDGET / reported
        #      capacity) — the PR-6 pre-flight's WARN upgraded to an
        #      automatic fallback. Uses a provisional Npad (the pallas
        #      chunk shrink below can only lower it, and stream forces the
        #      xla kernel anyway). ----
        self.residency = self._resolve_residency(
            config, per_target=per_target, chunk=chunk,
            cols_pad=cols_pad, code_itemsize=int(
                np.dtype(_kernel_dtype).itemsize),
            bins_pad=Bpad, bins_hist=_kernel_bins, slots=slots,
            num_leaves=num_leaves, num_models=K)
        if self.residency == "stream" and config.tpu_row_compact:
            # normalize the config to its EFFECTIVE semantics (stream runs
            # full streaming passes — no compaction) so the checkpoint
            # fingerprint covers what actually trains: a streamed run then
            # resumes into tpu_residency=device + tpu_row_compact=false
            # with bit-identical continued training
            config = config.replace(tpu_row_compact=False)
            self.config = config

        hist_kernel = config.tpu_hist_kernel
        if self.residency == "stream":
            # the streamed shard pass is the XLA one-hot matmul: the pallas
            # kernel only serves COMPACTED passes, and stream mode runs
            # full streaming passes by construction (row compaction needs
            # the packed row matrix device-resident — the very thing
            # streaming removes)
            if hist_kernel in ("pallas", "mixed"):
                Log.warning("tpu_residency=stream streams full histogram "
                            "passes through the xla kernel; overriding "
                            "tpu_hist_kernel=%s", hist_kernel)
            hist_kernel = "xla"
        if hist_kernel == "auto":
            # a function of committed code only: auto is the XLA one-hot
            # matmul on every platform. Whether the mixed dispatch (Pallas
            # for compacted passes) should become auto's TPU choice is a
            # measured decision (ROADMAP Queue 1 item 7); chip_smoke.py's
            # Pallas leg proves on every run that the kernel still compiles.
            hist_kernel = "xla"
            Log.debug("tpu_hist_kernel=auto resolved to %s", hist_kernel)
        if config.tpu_hist_f64 and hist_kernel in ("pallas", "mixed"):
            Log.warning("tpu_hist_f64 requires the xla histogram kernel; "
                        "overriding tpu_hist_kernel=%s", hist_kernel)
            hist_kernel = "xla"
        if hist_kernel == "pallas":
            # safely inside the 16MB scoped VMEM limit (2048-row chunks
            # OOM the in-kernel one-hot intermediates); chip_smoke.py's
            # Pallas leg compiles exactly this grid step
            chunk = min(chunk, 512)
        Npad = _round_up(per_target, chunk) * Drow
        self.num_data = N
        self.num_data_padded = Npad
        if (self._block_counts is not None and self.objective is not None
                and hasattr(self.objective, "set_row_layout")):
            # pre-partition: real rows sit at interleaved block positions,
            # not [0, N) — give structured objectives (lambdarank) the
            # global-row -> device-position map so their gathers stay valid
            self.objective.set_row_layout(
                np.asarray(self._real_rows()), Npad)

        self._num_bundles_padded = 0
        if bundle_plan is not None:
            # Bb_pad / cols_pad fixed above, with the kernel shape class
            Xb = bundle_plan.X_bundled
            self._num_bundles_padded = cols_pad
            fpad = F_pad - F
            ub = np.pad(bundle_plan.unpack_bin,
                        ((0, fpad), (0, Bpad - bundle_plan.unpack_bin.shape[1])),
                        constant_values=-1)
            from ..efb import build_code_feat
            from ..grower import BundleDecode
            cf = build_code_feat(bundle_plan, cols_pad, Bb_pad,
                                 meta["default_bin"].astype(np.int64))
            self.bundle = BundleDecode(
                col=self._put(np.pad(bundle_plan.col, (0, fpad))),
                lo=self._put(np.pad(bundle_plan.lo, (0, fpad))),
                hi=self._put(np.pad(bundle_plan.hi, (0, fpad))),
                off=self._put(np.pad(bundle_plan.off, (0, fpad))),
                unpack_bin=self._put(ub),
                code_feat=self._put(cf))
            self._hist_bins = Bb_pad
        else:
            self._hist_bins = 0
            if (train_set.deferred and self.residency != "stream"
                    and self._block_counts is None
                    and not self.pctx.multi_process):
                # device ingest engages: raw rows bin+pack on device in
                # the placement build below — host X_binned never exists
                Xb = None
            else:
                if train_set.deferred:
                    Log.info(
                        "deferred ingest falls back to host binning (%s)",
                        "stream residency" if self.residency == "stream"
                        else "pre-partitioned/multi-process layout")
                Xb = train_set.X_binned
        stage("booster.fingerprint")
        # dataset fingerprint for checkpoint/resume: the config fingerprint
        # deliberately excludes data PATHS, so a resumed run pointed at a
        # different dataset of the same shape must be caught here — a strided
        # sample of the binned codes plus the full label vector, hashed while
        # both are still host arrays (no device fetch, computed once)
        import hashlib
        _fp = hashlib.sha256()
        if Xb is None:
            # deferred device ingest: hash the SAME strided row sample the
            # host path would, binned through the host oracle (bin_rows is
            # byte-identical to X_binned[::stride]) — the fingerprint is
            # invariant to WHERE binning runs, so tpu_ingest stays a
            # checkpoint-VOLATILE knob
            _shape0, _shape1 = train_set.num_data, train_set.num_features
            _fp.update(np.int64([N, _shape0, _shape1]).tobytes())
            _stride = max(1, _shape0 // 256)
            _fp.update(train_set.bin_rows(
                np.arange(0, _shape0, _stride)).tobytes())
        else:
            _fp.update(np.int64([N, Xb.shape[0], Xb.shape[1]]).tobytes())
            _stride = max(1, Xb.shape[0] // 256)
            _fp.update(np.ascontiguousarray(Xb[::_stride]).tobytes())
        _fp.update(np.asarray(meta_global.label, np.float32).tobytes())
        self._data_fingerprint = _fp.hexdigest()

        stage("booster.place_codes")
        # device placement of the (possibly bundled) code matrix: rows padded
        # to Npad (equal per-process blocks under pre-partition, where only
        # the LOCAL shard exists on this host), columns to the strategy pad.
        # Placement goes through the Dataset's residency cache
        # (dataset.device_put_cached): the sharded code matrix and padding
        # mask are immutable step CONSTANTS, so every booster built over the
        # same mesh/padding reuses the same on-device buffers — the binned
        # dataset lives on the mesh once, not once per booster.
        _ncols = Xb.shape[1] if Xb is not None else train_set.num_features
        col_pad = (0, cols_pad - _ncols)
        self._stream_store = None
        self._stream = None
        self._streamed_grower = None
        self._stream_fns = None
        self._ingest_report = None
        self._comm_bytes_per_wave = {}      # comm.collective_bytes, by name
        if self.residency == "stream":
            # out-of-core: the padded (possibly bundled) code matrix is cut
            # into fixed-size host shards, packed to the tightest byte
            # layout the bin range allows (u4 at <16 bins — the
            # "compressed bin codes" of arXiv 1806.11248), and NEVER
            # device_put whole. The shard size divides the padded
            # per-device rows exactly, so Npad, every chunk boundary, and
            # the bagging RNG shapes are identical to device residency —
            # the bit-identity contract (tests/test_stream.py).
            from ..ops.stream import (HostShardStore, ShardPrefetcher,
                                      resolve_shard_rows)
            from ..ops.histogram import code_mode_for
            shard_devs = (self.pctx.num_devices
                          if self.pctx.mesh is not None
                          and self.pctx.strategy in ("data", "voting")
                          else 1)
            local_rd = resolve_shard_rows(Npad // shard_devs, chunk,
                                          config.tpu_stream_shard_rows)
            _max_code = (bundle_plan.max_bundle_bins
                         if bundle_plan is not None
                         else train_set.max_num_bin)
            # the store pads per block at pack time — no full padded copy
            # of a matrix that by definition outgrows memory budgets
            self._stream_store = HostShardStore(
                Xb, n_rows_padded=Npad, num_cols=cols_pad,
                local_shard_rows=local_rd, n_devices=shard_devs,
                code_mode=code_mode_for(int(_max_code), Xb.dtype))
            # chaos hook (robustness/chaos.py): a marker-gated one-shot
            # bit flip right after packing, so the per-shard CRC path is
            # exercisable end-to-end; no-op without the env knob
            from ..robustness.chaos import maybe_corrupt_shard_from_env
            maybe_corrupt_shard_from_env(self._stream_store)
            self._stream = ShardPrefetcher(
                self._stream_store, lambda a: self._put(a, "rows0"),
                verify=config.tpu_stream_verify)
            self.Xb = None
            sd = self._stream_store.describe()
            Log.info(
                "tpu_residency=stream: codes in %d host shards x %d rows "
                "(%s-packed, %.1f MB/shard, %.2f GB total); H2D double-"
                "buffered through the wave loop, row compaction off "
                "(full streaming passes)", sd["n_shards"],
                sd["shard_rows"], sd["code_mode"],
                sd["shard_bytes"] / (1 << 20), sd["total_bytes"] / (1 << 30))
        elif self._block_counts is not None:
            bp = Npad // len(self._block_counts)
            self.Xb = self._put_rows0_local(
                np.pad(Xb, ((0, bp - Xb.shape[0]), col_pad)), Npad)
        else:
            bundle_sig = None
            if bundle_plan is not None:
                # the bundled matrix's content is a pure function of the
                # plan — fingerprint its column maps, not the N*G codes
                import zlib
                bundle_sig = (
                    int(bundle_plan.num_groups),
                    int(bundle_plan.max_bundle_bins),
                    zlib.crc32(np.ascontiguousarray(bundle_plan.col).tobytes()),
                    zlib.crc32(np.ascontiguousarray(bundle_plan.off).tobytes()))
            # the cache key is IDENTICAL for host and device ingest — both
            # produce bit-identical placed codes, so a booster switching
            # tpu_ingest reuses the same on-device buffers
            _code_dtype = Xb.dtype if Xb is not None else train_set.code_dtype
            if Xb is None:
                _build = lambda: self._ingest_device(  # noqa: E731
                    train_set, N, Npad, cols_pad)
            else:
                _build = lambda: self._put(  # noqa: E731
                    np.pad(Xb, ((0, Npad - N), col_pad)), "rows0")
            self.Xb = train_set.device_put_cached(
                ("Xb", Npad, cols_pad, str(_code_dtype), bundle_sig,
                 self.pctx.residency_key()), _build)
        stage("booster.place")
        self.label = self._put(self._row_layout(meta_global.label, Npad), "rows")
        w = meta_global.weight
        self.weight = None if w is None else self._put(
            self._row_layout(w, Npad), "rows")
        if self._block_counts is None:
            self.pad_mask = train_set.device_put_cached(
                ("pad_mask", Npad, N, self.pctx.residency_key()),
                lambda: self._put(self._row_layout(np.ones(N, np.float32),
                                                   Npad), "rows"))
        else:
            self.pad_mask = self._put(
                self._row_layout(np.ones(N, np.float32), Npad), "rows")

        fpad = F_pad - F
        self.num_bins = self._put(np.pad(meta["num_bins"], (0, fpad), constant_values=1))
        self.missing_code = self._put(np.pad(meta["missing_code"], (0, fpad)))
        self.default_bin = self._put(np.pad(meta["default_bin"], (0, fpad)))
        self.is_categorical_np = meta["is_categorical"]
        is_cat_pad = np.pad(meta["is_categorical"], (0, fpad))
        self.is_cat = self._put(is_cat_pad)
        ok = np.arange(F_pad) < F                           # padding features off
        self.feature_ok_base = self._put(ok)

        stage("booster.spec")
        # packed-row code layout for the compacted gather: nibble-pack two
        # codes/byte at <=16 bins, 6-bit-pack four codes/3 bytes at <=64
        # (the reference's Dense4bitsBin analog, dense_nbits_bin.hpp:37, and
        # its own GPU bench config max_bin=63). The Pallas kernel's in-kernel
        # unpack handles plain byte layouts only — keep u8/u16 there.
        from ..ops.histogram import (code_mode_for, default_code_mode,
                                     one_leaf_break_even, one_leaf_form,
                                     packed_row_bytes)
        from ..ops.pallas_histogram import one_leaf_runs_on
        max_code = (bundle_plan.max_bundle_bins if bundle_plan is not None
                    else train_set.max_num_bin)
        _xb_dtype = Xb.dtype if Xb is not None else train_set.code_dtype
        if hist_kernel in ("pallas", "mixed"):
            code_mode = default_code_mode(_xb_dtype)
        else:
            code_mode = code_mode_for(int(max_code), _xb_dtype)

        # stream or compact a wave's histogram pass: tpu_compact_frac, 0 =
        # auto = the break-even of the two arms' costs at THIS shape (build
        # width x bins, the packed row's bytes, the weight mode, rows a
        # device), a Python float resolved once, here
        _exact = bool(config.tpu_hist_f64)
        _row_bytes = packed_row_bytes(_hist_cols, code_mode, _exact)
        compact_frac = resolve_compact_frac(
            config.tpu_compact_frac, hist_kernel,
            rows=Npad // Drow, features=_hist_cols, bins_padded=_kernel_bins,
            row_bytes=_row_bytes, num_slots=slots, exact=_exact)
        # the one-leaf form of the chunk matmul (a wave that holds one
        # pending leaf: the root's pass, its smaller child's): where the
        # build's shapes have one, beside the xla kernel's bf16 hi/lo mode
        # of a resident pass, on the device its Mosaic kernel exists for.
        # Its stream-or-compact threshold is its own break-even
        _one_leaf = (one_leaf_form(_hist_cols, _kernel_bins, chunk)
                     if (hist_kernel == "xla" and not _exact
                         and self.residency != "stream"
                         and one_leaf_runs_on(self.pctx.devices[0].platform))
                     else None)
        one_leaf_frac = 0.0 if _one_leaf is None else one_leaf_break_even(
            Npad // Drow, _one_leaf, _row_bytes, slots)
        self._hist_acc_bytes_one_leaf = (
            0 if _one_leaf is None else _one_leaf.acc_bytes)
        wave = config.tpu_wave_size or slots
        self.spec = GrowerSpec(
            num_leaves=num_leaves,
            num_features=F_pad,
            num_bins_padded=Bpad,
            chunk_rows=chunk,
            hist_slots=slots,
            wave_size=min(wave, slots),
            max_depth=config.max_depth,
            lambda_l1=config.lambda_l1,
            lambda_l2=config.lambda_l2,
            min_data_in_leaf=float(config.min_data_in_leaf),
            min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
            min_gain_to_split=config.min_gain_to_split,
            # stream mode runs full streaming passes: compaction gathers
            # rows from a device-resident packed matrix — the very
            # allocation streaming removes. Bit-identity is therefore
            # against device residency with tpu_row_compact=false.
            row_compact=(config.tpu_row_compact
                         and self.residency != "stream"),
            compact_frac=compact_frac,
            one_leaf_frac=one_leaf_frac,
            hist_kernel=hist_kernel,
            hist_f64=_exact,
            hist_bins=self._hist_bins,
            efb_unpack=(self.bundle is not None and self._efb_unpack),
            code_mode=code_mode,
            use_categorical=bool(meta["is_categorical"].any()),
            cat_features=tuple(int(i) for i in np.nonzero(is_cat_pad)[0]),
            cat_smooth=config.cat_smooth,
            cat_l2=config.cat_l2,
            max_cat_threshold=config.max_cat_threshold,
            max_cat_to_onehot=config.max_cat_to_onehot,
            min_data_per_group=float(config.min_data_per_group),
        )
        self.comm = self.pctx.make_comm(
            F_pad,
            # bundle blocks are the partition unit for feature-parallel
            # (both EFB arms) and for data-parallel on the NATIVE arm,
            # where the psum_scatter itself runs in bundle space
            num_bundles=(self._num_bundles_padded
                         if (self.pctx.strategy == "feature"
                             or (self.pctx.strategy == "data"
                                 and self.bundle is not None
                                 and not self._efb_unpack)) else 0),
            bundle_col=None if self.bundle is None else self.bundle.col)
        if self.residency == "stream":
            from ..grower import StreamedGrower
            self._streamed_grower = StreamedGrower(
                self.spec, self.pctx, self.comm,
                n_rows_padded=Npad,
                local_shard_rows=self._stream_store.local_shard_rows,
                n_shards=self._stream_store.n_shards,
                num_cols=cols_pad, code_mode=self._stream_store.code_mode,
                num_bins=self.num_bins, missing_code=self.missing_code,
                default_bin=self.default_bin, is_cat=self.is_cat,
                bundle=self.bundle)

        # ---- piecewise-linear leaves (linear_tree=true, ops/linear.py) -----
        # the per-leaf ridge fit reads RAW f32 feature values the binned
        # matrix discards: a NaN-sanitized [Npad, F_pad] slice plus its
        # missing plane become step constants (cached on the dataset like
        # Xb). v1 scope: single-device, non-streamed, row-replicated —
        # every unsupported combination rejects loudly here, never trains
        # silently-wrong coefficients.
        self.linear_tree = bool(config.linear_tree)
        self.Xraw = None
        self.Xmiss = None
        self._linear_max_steps = 1
        if self.linear_tree:
            if self.pctx.strategy == "feature":
                Log.fatal("linear_tree=true is not supported with "
                          "tree_learner=feature (the raw-feature slice is "
                          "row-aligned; use serial)")
            if self.pctx.num_devices > 1 or self.pctx.multi_process:
                Log.fatal("linear_tree=true is single-device for now "
                          "(%d devices requested): the per-leaf moment "
                          "accumulation is not wired through the mesh "
                          "collectives yet", self.pctx.num_devices)
            if config.is_pre_partition:
                Log.fatal("linear_tree=true is not supported with "
                          "is_pre_partition")
            raw_np = getattr(train_set, "X_raw", None)
            if raw_np is None:
                Log.fatal("linear_tree=true needs the dataset's raw feature "
                          "slice, which this dataset was constructed "
                          "without — rebuild the Dataset with "
                          "linear_tree=true in its params (binary dataset "
                          "files save it only when written under "
                          "linear_tree)")
            raw_pad = np.zeros((Npad, F_pad), np.float32)
            raw_pad[:N, :F] = raw_np
            miss_pad = np.isnan(raw_pad)
            np.nan_to_num(raw_pad, copy=False, nan=0.0,
                          posinf=np.float32(np.finfo(np.float32).max),
                          neginf=np.float32(np.finfo(np.float32).min))
            self.Xraw = train_set.device_put_cached(
                ("Xraw", Npad, F_pad, self.pctx.residency_key()),
                lambda: self._put(raw_pad, "rows0"))
            self.Xmiss = train_set.device_put_cached(
                ("Xmiss", Npad, F_pad, self.pctx.residency_key()),
                lambda: self._put(miss_pad, "rows0"))
            # path depth bound for the leaf->root feature walk
            depth_cap = config.max_depth if config.max_depth > 0 \
                else num_leaves - 1
            self._linear_max_steps = max(1, min(num_leaves - 1, depth_cap))
            Log.info("linear_tree: per-leaf ridge solves on (lambda=%g, "
                     "max_features=%d); raw slice %.2f MB + %.2f MB missing "
                     "plane device-resident", config.linear_lambda,
                     config.linear_max_features,
                     raw_pad.nbytes / (1 << 20), miss_pad.nbytes / (1 << 20))

        # feature_fraction: number of features used per tree
        self.n_feature_sample = max(1, int(round(config.feature_fraction * F)))
        self.use_feature_fraction = config.feature_fraction < 1.0 and self.n_feature_sample < F

        self.train_metrics = create_metrics(config, self.objective.name if self.objective else None)
        for m in self.train_metrics:
            m.init(meta_global, N)
        self.valid_sets: List[ValidSet] = []

        # ---- initial scores -------------------------------------------------
        stage("booster.init_score")
        self.init_score_value = 0.0
        # meta_global, not train_set.metadata: under pre-partition the local
        # shard only holds its own init_score slice
        meta_is = meta_global.init_score
        has_init = meta_is is not None
        if (config.boost_from_average and not has_init and K == 1
                and self.objective is not None):
            avg = self.objective.boost_from_average_score()
            if avg is not None and abs(avg) > 1e-15:
                self.init_score_value = float(avg)

        base = np.full((K, Npad), self.init_score_value, dtype=np.float32)
        if has_init:
            is_arr = np.asarray(meta_is, dtype=np.float32).reshape(K, N, order="C") \
                if len(meta_is) == K * N else np.tile(np.asarray(meta_is, np.float32), (K, 1))
            # _row_layout, not [:N]: real rows sit at block positions under
            # pre-partition
            base += np.stack([self._row_layout(is_arr[k], Npad)
                              for k in range(K)])
        self.score = self._put(base, "rows1")

        stage("booster.state")
        self.models: List[List] = []        # per iteration: list of K device TreeArrays
        self._grow_records: List[GrowRecord] = []   # per iteration, on device
        self._step_traces = 0               # times jax traced a step body
        self._trace_signatures: Dict[str, Dict[str, str]] = {}   # per site
        self.iter_ = 0
        # telemetry high-water mark: iterations already counted into the
        # monotonic trees.trained/rows.routed/grow.* metrics
        # (_publish_grow_records, where the trees come to the host).
        # Checkpoint restore and repeated train() calls on one booster bump
        # it so restored/already-published iterations are never re-counted.
        self._telemetry_iters_base = 0
        # monotonic forest-content counter: iter_ alone can collide after a
        # rollback (explicit or the no-splits pop) followed by a retrain,
        # which would let stale materialized host trees pass a length check
        self.mutations_ = 0
        # device-resident twins of the per-step host scalars: every
        # host->device scalar is a transfer the dispatch has to wait for —
        # the step carries its own iteration counter and only re-uploads
        # the shrinkage when a learning_rates schedule actually changes it
        self._iter_dev = None               # i32, step output; None = resync
        self._shrink_cache = (None, None)   # (float value, device scalar)
        self.best_iter: Dict[str, int] = {}
        self.best_score: Dict[str, float] = {}
        self._rng_key = self._put(
            jax.random.PRNGKey(config.seed if config.seed else config.bagging_seed))

        self.bagging_on = config.bagging_freq > 0 and config.bagging_fraction < 1.0
        # under bagging the carried mask is DONATED to the step (XLA updates
        # it in place) — it must own its buffer, never alias pad_mask, which
        # travels separately as a step constant
        self.bag_mask = self.pad_mask + 0 if self.bagging_on else self.pad_mask
        self.best_iteration = 0

        # non-finite guard (robustness/numeric.py): a trace-time constant —
        # "none" compiles the exact unguarded step program
        self.nan_policy = config.nan_policy
        self._consecutive_skips = 0

        self._step_fn = None
        self._custom_step_fn = None

        # ---- fused multi-tree dispatch (tree_batch) ------------------------
        # K boosting iterations per jit dispatch via lax.scan: grad/hess,
        # tree growth, and score updates for K trees never leave HBM, and
        # the host pays dispatch overhead once per K trees. Requires the
        # whole per-iteration pipeline to be device-resident, which dart
        # (host-side drop-set selection) and goss (conservatively, per its
        # sampling contract) opt out of via supports_tree_batch.
        tb = max(1, config.tree_batch)
        if tb > 1 and not self.supports_tree_batch:
            Log.warning(
                "tree_batch=%d is not supported with boosting=%s (the "
                "per-iteration pipeline is not fully device-resident); "
                "falling back to tree_batch=1", tb,
                config.boosting_normalized)
            tb = 1
        if tb > 1 and self.residency == "stream":
            # pinned in tests/test_stream.py: the shard loop is driven by
            # the host per wave — fusing K iterations under one lax.scan
            # would trap the H2D transfers inside a traced body, which is
            # exactly what tpu-lint R009 forbids
            Log.warning(
                "tree_batch=%d is not supported with tpu_residency=stream "
                "(the shard prefetch loop is host-driven); falling back "
                "to tree_batch=1", tb)
            tb = 1
        if (tb > 1 and self.average_output
                and config.nan_policy in ("raise", "skip_iter")):
            # RF's running-average score weights by the device iteration
            # counter, which keeps advancing through a batch: a mid-batch
            # gated no-op would leave phantom iterations in the average
            # denominator (skip_iter), and raise's rollback would need
            # trailing trees subtracted — rejected for average_output.
            # The K=1 paths resync the counter and stay exact.
            Log.warning(
                "tree_batch=%d with nan_policy=%s cannot compose with a "
                "mid-batch skip/rollback under boosting=rf (scores are "
                "running averages weighted by the iteration counter); "
                "falling back to tree_batch=1", tb, config.nan_policy)
            tb = 1
        self.tree_batch = tb
        self._batch_step_fns: Dict[int, object] = {}

        # telemetry: the resolved kernel choice and dispatch shape of this
        # booster (observability registry + an instant trace event) — the
        # per-booster facts the next perf session reads first
        reg = obs.get_registry()
        reg.counter(f"booster.kernel.{hist_kernel}").inc()
        reg.counter(f"booster.residency.{self.residency}").inc()
        reg.gauge("booster.tree_batch").set(tb)
        reg.gauge("booster.wave_size").set(self.spec.wave_size)
        reg.gauge("booster.hist_slots").set(self.spec.hist_slots)
        # the histogram pass as the shapes sized it: rows a chunk, the
        # one-hot operand of one chunk's matmul (what the rule bounds; the
        # TPU allocates none of it), the f32 accumulator every chunk reads
        # and writes once
        self._hist_acc_bytes = (
            _hist_cols * _kernel_bins * self.spec.hist_slots
            * num_channels(_exact) * 4)
        reg.gauge("hist.chunk_rows").set(self.spec.chunk_rows)
        reg.gauge("hist.onehot_bytes").set(
            self.spec.chunk_rows * _hist_cols * _kernel_bins
            * (4 if self.spec.hist_f64 else 2))
        reg.gauge("hist.acc_bytes").set(self._hist_acc_bytes)
        reg.gauge("hist.compact_frac").set(self.spec.compact_frac)
        # 0 where no wave takes the one-leaf form
        reg.gauge("hist.one_leaf_frac").set(self.spec.one_leaf_frac)
        reg.gauge("hist.one_leaf_acc_bytes").set(self._hist_acc_bytes_one_leaf)
        # slot pairs a block of the wave's tail covers (the cache's
        # write-back and the split scan): hist_slots = the static form
        reg.gauge("scan.block_slots").set(scan_block_pairs(
            self.spec.hist_slots, *scan_hist_shape(
                self.spec, self.comm, _hist_cols, self.bundle is not None)))
        # the routing pass's one-hot is booster.hist_slots wide, and every
        # row reads this many table columns through it
        reg.gauge("route.table_cols").set(
            route_table_cols(self.spec, self.bundle))
        obs.event("hist_pass_shape", rule=_shape_rule,
                  chunk_rows=int(self.spec.chunk_rows),
                  rows=int(per_target), features=int(_hist_cols),
                  bins=int(_kernel_bins),
                  compact_frac=float(self.spec.compact_frac),
                  compact_rule=("explicit" if config.tpu_compact_frac
                                else "auto"))
        if self._stream_store is not None:
            reg.gauge("stream.n_shards").set(self._stream_store.n_shards)
            reg.gauge("stream.shard_bytes").set(
                self._stream_store.shard_bytes)
        obs.event("booster_init", kernel=hist_kernel, tree_batch=tb,
                  rows=int(N), features=int(F), num_leaves=int(num_leaves),
                  strategy=self.pctx.strategy, nan_policy=self.nan_policy,
                  mesh_axis=self.pctx.axis_kind,
                  n_devices=self.pctx.num_devices,
                  residency=self.residency)
        if self._stream_store is not None:
            obs.event("stream_init", **self._stream_store.describe())
        # MULTICHIP story: the resolved mesh (device count + which dataset
        # axis it shards — the tree_learner=auto outcome) and the analytic
        # per-wave collective payload estimates (parallel/comm.py
        # collective_bytes) — host arithmetic at construction, so the comm
        # budget is inspectable before any distributed dispatch runs
        reg.gauge("comm.mesh.n_devices").set(self.pctx.num_devices)
        reg.gauge("comm.mesh.rows_sharded").set(
            1 if self.pctx.axis_kind == "rows" else 0)
        reg.counter(f"booster.tree_learner.{self.pctx.strategy}").inc()
        if self.pctx.mesh is not None:
            obs.event("mesh_axes", **self.pctx.describe())
        comm_bytes = self.comm.collective_bytes(
            self.spec.hist_slots, Bpad,
            use_categorical=self.spec.use_categorical,
            # native bundled runs move BUNDLE-space histograms through the
            # wave collectives; the legacy unpack arm reduces feature-space
            # histograms (unbundle-early), so it keeps the default widths
            hist_bins=(self._hist_bins
                       if (self.bundle is not None and not self._efb_unpack)
                       else None))
        if comm_bytes and counts_past_f32(
                self.num_data_padded // self.pctx.pad_rows_multiple(),
                self.comm):
            # past 2^24 rows the tree's counts are summed again as integers
            comm_bytes["psum_leaf_counts"] = (num_leaves + 1) * 4
        self._comm_bytes_per_wave = comm_bytes
        for cname, nbytes in comm_bytes.items():
            reg.gauge(f"comm.bytes_per_wave.{cname}").set(nbytes)
        if comm_bytes:
            obs.event("comm_cost", strategy=self.pctx.strategy, **comm_bytes)

    # ------------------------------------------------------------------ setup

    # out-of-core streaming capability (tpu_residency=stream): the whole
    # per-iteration pipeline must be drivable through the host-side shard
    # loop; DART opts out (host-side drop-set selection reads the resident
    # code matrix per tree via _contrib_fn)
    supports_stream = True

    def _stream_support(self, config) -> Tuple[bool, str]:
        """(supported, why-not) for tpu_residency=stream under this
        booster's strategy/topology — consulted by the residency
        resolution (forced stream fails loudly; auto never picks an
        unsupported mode)."""
        if not self.supports_stream:
            return False, (f"boosting={config.boosting_normalized} keeps "
                           f"host-side per-tree state that reads the "
                           f"resident code matrix")
        if getattr(config, "linear_tree", False):
            return False, ("linear_tree=true keeps the raw feature slice "
                           "device-resident (the per-leaf fits read raw "
                           "values every tree)")
        if self.pctx.strategy == "feature":
            return False, ("tree_learner=feature replicates rows and "
                           "slices columns at trace time; stream shards "
                           "rows (use data/voting)")
        if self.pctx.multi_process:
            return False, ("multi-host execution streams per-process "
                           "shards is not wired yet (single-process "
                           "meshes only)")
        if config.is_pre_partition:
            return False, "is_pre_partition holds per-process row blocks"
        return True, ""

    def _resolve_residency(self, config, *, per_target: int, chunk: int,
                           cols_pad: int, code_itemsize: int,
                           bins_pad: int, bins_hist: int, slots: int,
                           num_leaves: int, num_models: int) -> str:
        """Resolve ``tpu_residency`` before any device placement.

        ``auto`` compares an analytic DEVICE-residency estimate
        (observability/memory.py estimate_wave_residency, the PR-6
        pre-flight model at provisional padding) against the per-device
        HBM budget and falls back to ``stream`` when it does not fit —
        the warning the pre-flight used to stop at, turned into the fix.
        The decision estimate sizes the histogram cache at full width
        (conservative under data-parallel's block-sharded cache: an
        overestimate can only stream earlier, never OOM later)."""
        from ..observability.memory import (estimate_wave_residency,
                                            hbm_budget_bytes)
        requested = config.tpu_residency
        if requested == "device":
            return "device"
        supported, why = self._stream_support(config)
        if requested == "stream":
            if not supported:
                Log.fatal("tpu_residency=stream is not supported here: %s",
                          why)
            return "stream"
        # auto: estimate full-N device residency per device
        budget = hbm_budget_bytes(config)
        if budget is None:
            return "device"
        rows = _round_up(per_target, chunk)   # padded PER-DEVICE rows
        channels, chb = (3, 4) if config.tpu_hist_f64 else (5, 2)
        packed_row_bytes = 0
        if config.tpu_row_compact:
            from ..ops.histogram import code_bytes_total
            mode = "u16" if code_itemsize == 2 else "u8"
            packed_row_bytes = (code_bytes_total(cols_pad, mode)
                                + channels * chb)
        est = estimate_wave_residency(
            rows=rows, cols=cols_pad, code_itemsize=code_itemsize,
            num_models=num_models, num_leaves=num_leaves,
            hist_cols=cols_pad, hist_bins=bins_hist, cache_cols=cols_pad,
            cache_bins=bins_hist, num_bins_padded=bins_pad, slots=slots,
            chunk_rows=chunk, channels=channels, channel_bytes=chb,
            packed_row_bytes=packed_row_bytes,
            row_compact=config.tpu_row_compact,
            bagging=(config.bagging_freq > 0
                     and config.bagging_fraction < 1.0),
            tree_batch=max(1, config.tree_batch),
            linear_max_features=(config.linear_max_features
                                 if config.linear_tree else 0))
        if est["total_bytes"] <= budget:
            return "device"
        gb = 1 << 30
        if not supported:
            Log.warning(
                "HBM pre-flight: estimated device residency %.3g GB "
                "exceeds the %.3g GB budget but tpu_residency=stream is "
                "unavailable (%s) — staying device-resident; expect an "
                "OOM at first dispatch", est["total_bytes"] / gb,
                budget / gb, why)
            return "device"
        Log.warning(
            "HBM pre-flight: estimated device residency %.3g GB exceeds "
            "the %.3g GB per-device budget — auto-selecting "
            "tpu_residency=stream: the binned codes stay in host-resident "
            "packed shards and stream H2D double-buffered through the "
            "wave loop (docs/TPU-Performance.md \"Out-of-core streaming\")",
            est["total_bytes"] / gb, budget / gb)
        return "stream"

    def _real_rows(self):
        """Index of real (non-padding) rows in the padded device layout, in
        global row order — a plain slice normally, the per-process block
        positions under pre-partition (where [:N] would pick block-0 padding
        and drop block-1's tail)."""
        if self._block_counts is None:
            return slice(0, self.num_data)
        bp = self.num_data_padded // len(self._block_counts)
        return np.concatenate([np.arange(c) + p * bp
                               for p, c in enumerate(self._block_counts)])

    def _row_layout(self, arr, npad: Optional[int] = None, fill=0):
        """Host row array (global row order) -> padded device layout.

        Normally: data first, padding at the tail. Under pre-partition: equal
        per-process blocks of Npad/P rows, each process's rows at the head of
        its block — matching `_put_rows0_local`'s placement of the local
        feature matrix, so row i of the label/mask lines up with row i of X.
        """
        arr = np.asarray(arr)
        npad = self.num_data_padded if npad is None else npad
        out = np.full((npad,) + arr.shape[1:], fill, arr.dtype)
        if self._block_counts is None:
            out[: arr.shape[0]] = arr
        else:
            bp = npad // len(self._block_counts)
            off = 0
            for p, c in enumerate(self._block_counts):
                out[p * bp: p * bp + c] = arr[off: off + c]
                off += c
        return out

    def _put_rows0_local(self, local_block: np.ndarray, npad: int):
        """Assemble the global row-sharded [Npad, F] array from this
        process's padded block — no process ever holds the others' features
        (jax.make_array_from_process_local_data; the reference's
        pre-partitioned load keeps shards local the same way)."""
        sharding = self.pctx.sharding("rows0")
        return jax.make_array_from_process_local_data(
            sharding, local_block, (npad, local_block.shape[1]))

    def _put(self, x, kind: str = "repl"):
        """Place an array on this booster's device(s) with the mesh-resident
        NamedSharding the strategy's axis role dictates
        (``ParallelContext.sharding``): "rows" ([N] sharded), "rows0"
        ([N, F] rows on dim 0), "rows1" ([K, N] rows on dim 1), "repl"
        (replicated). Row sharding only applies to row-partitioned
        strategies (data/voting); the feature strategy replicates rows like
        the reference's FeatureParallel learner (every machine holds all
        data, feature_parallel_tree_learner.cpp)."""
        pctx = self.pctx
        sharding = pctx.sharding(kind)
        if not isinstance(x, jax.Array):
            # host values go to their shards straight from host memory — a
            # jnp.asarray here would stage the WHOLE array on device 0 first
            x = np.asarray(x)
        if sharding is None:
            return jax.device_put(x, pctx.devices[0])
        if pctx.multi_process:
            # every process holds the full (host) array; materialize only the
            # locally-addressable shards of the global sharded array — the
            # multi-host analog of the reference's non-pre-partitioned load
            # (dataset_loader.cpp:159 rank/num_machines row partitioning)
            x = np.asarray(x)
            return jax.make_array_from_callback(x.shape, sharding,
                                                lambda idx: x[idx])
        return jax.device_put(x, sharding)

    def _ingest_device(self, train_set, N: int, Npad: int, cols_pad: int):
        """Bin + pack the deferred raw rows on device (ops/ingest.py) —
        the build closure of the Xb residency cache when device ingest
        engages. Bit-identical to host binning + ``np.pad`` + ``_put``
        (tests/test_ingest.py). Under a row-sharded mesh every device
        ingests ITS OWN row block straight from host memory, so no chip
        ever holds more than its shard; the feature strategy replicates
        rows, so it bins once and broadcasts device-to-device."""
        from ..ops.ingest import device_ingest, merge_ingest_reports
        cfg = self.config
        raw = train_set.deferred_raw()
        sharding = self.pctx.sharding("rows0")
        if sharding is not None and self.pctx.strategy != "feature":
            blocks = [(dev, idx[0].indices(Npad)[:2]) for dev, idx in
                      sharding.addressable_devices_indices_map(
                          (Npad, cols_pad)).items()]
        else:
            blocks = [(self.pctx.devices[0], (0, Npad))]
        shards, reports = [], []
        for dev, (lo, hi) in blocks:
            arr, rep = device_ingest(
                raw[lo:hi], train_set.mappers,
                np.asarray(train_set.real_feature_idx),
                n_rows=max(0, min(hi, N) - lo), n_rows_padded=hi - lo,
                num_cols=cols_pad, out_dtype=train_set.code_dtype,
                chunk_rows=int(cfg.tpu_ingest_chunk_rows), device=dev,
                prefetch_depth=int(cfg.tpu_ingest_prefetch))
            shards.append(arr)
            reports.append(rep)
        report = merge_ingest_reports(reports)
        self._ingest_report = report
        if len(reports) > 1:
            # the ingest span ran once a device: its gauge held the last one's
            reg = obs.get_registry()
            reg.gauge("setup.ingest_s").set(report["seconds"])
            for d, rep in enumerate(reports):
                reg.gauge(f"setup.ingest_device_s.{d}").set(rep["seconds"])
        Log.info("device ingest: %d rows binned+packed on %d device(s) "
                 "(%.2f Mrow/s, %d chunks, stall fraction %.2f)",
                 N, len(blocks), (report["rows_per_s"] or 0.0) / 1e6,
                 report["n_chunks"], report["stall_fraction"])
        if len(blocks) > 1:
            return jax.make_array_from_single_device_arrays(
                (Npad, cols_pad), sharding, shards)
        if sharding is not None:
            return jax.device_put(shards[0], sharding)
        return shards[0]

    def add_valid(self, name: str, binned: np.ndarray, metadata: Metadata,
                  raw: Optional[np.ndarray] = None) -> None:
        nv = binned.shape[0]
        metrics = create_metrics(self.config, self.objective.name if self.objective else None)
        for m in metrics:
            m.init(metadata, nv)
        F_pad = self.spec.num_features
        if binned.shape[1] < F_pad:
            binned = np.pad(binned, ((0, 0), (0, F_pad - binned.shape[1])))
        vs = ValidSet(name, self._put(binned), metadata, metrics, nv)
        if self.linear_tree:
            # the valid-score updates run the linear epilogue — they need
            # the same sanitized raw slice the training rows carry
            if raw is None:
                Log.fatal("linear_tree=true: valid set %r needs its raw "
                          "feature values (construct it with "
                          "free_raw_data=False)", name)
            raw_pad = np.zeros((nv, F_pad), np.float32)
            raw_pad[:, : raw.shape[1]] = np.asarray(raw, np.float32)
            miss_pad = np.isnan(raw_pad)
            np.nan_to_num(raw_pad, copy=False, nan=0.0)
            vs.Xraw = self._put(raw_pad)
            vs.Xmiss = self._put(miss_pad)
        base = np.full((self.num_models, nv), self.init_score_value, dtype=np.float32)
        if metadata.init_score is not None:
            base += np.asarray(metadata.init_score, np.float32).reshape(
                self.num_models, nv)
        vs.score = self._put(base)
        self.valid_sets.append(vs)

    # ------------------------------------------------------------- train step

    def _gradients(self, score):
        """Hook: GOSS/DART/RF override pieces of this pipeline."""
        label = self.label
        g, h = self.objective.gradients(score, label, self.weight)
        return g, h

    def _bag_mask_for_iter(self, key, it, prev_mask):
        if not self.bagging_on:
            return self.pad_mask
        resample = (it % self.config.bagging_freq) == 0
        bern = jax.random.uniform(key, (self.num_data_padded,)) < self.config.bagging_fraction
        new_mask = bern.astype(jnp.float32) * self.pad_mask
        return jnp.where(resample, new_mask, prev_mask)

    @property
    def samples_rows(self) -> bool:
        """Whether the step draws a row sample: static for a compiled step
        (bagging is on; GOSS always). Such a step hands the grower its mask
        as the histogram's row set (``grow_tree(sampled=True)``) and counts
        the sample (``SampleStats``); any other step is the program it was."""
        return self.bagging_on

    def _sampling(self, g, h, bag_mask, key, it):
        """Row-sampling hook: returns (mask, g, h, SampleStats or None).
        Base = bagging; GOSS overrides with gradient-based one-side sampling
        (goss.hpp:86-131)."""
        mask = self._bag_mask_for_iter(key, it, bag_mask)
        if not self.samples_rows:
            return mask, g, h, None
        return mask, g, h, SampleStats.count(jnp.zeros((), bool), mask, mask)

    def _tree_output_transform(self, tree):
        """Hook: RF converts leaf outputs via the objective (rf.hpp:160-167)."""
        return tree

    def _score_update(self, old_score_k, contrib, it):
        """Hook: base adds; RF maintains a running average (rf.hpp:117-121)."""
        return old_score_k + contrib

    # Per-tree math blocks shared VERBATIM by the resident ``step_body``
    # and the streamed step legs (``_make_stream_fns``) — like the grower's
    # ``_apply_wave_splits``, each has exactly one home so the two
    # residency modes cannot drift apart (the bit-identity contract of
    # tests/test_stream.py). All three are traced inside whichever jit
    # calls them.

    def _feature_mask(self, fkey, k):
        """Per-model feature_fraction mask (serial_tree_learner.cpp:240)."""
        if not self.use_feature_fraction:
            return self.feature_ok_base
        fk = jax.random.fold_in(fkey, k)
        noise = jax.random.uniform(fk, (self.spec.num_features,))
        # padding features must not consume sample slots
        noise = jnp.where(self.feature_ok_base, noise, -1.0)
        _, top_idx = jax.lax.top_k(noise, self.n_feature_sample)
        fmask = jnp.zeros(self.spec.num_features, bool).at[top_idx].set(True)
        return fmask & self.feature_ok_base

    def _shrink_transform_flag(self, tree, shrinkage):
        """Shrinkage + output transform + (under nan_policy) the leaf
        non-finite flag and clip. Returns ``(tree, bad_leaf_or_None)``.
        Reference Tree::Shrinkage scales internal_value_ too
        (tree.h:137-142) — TreeSHAP reads node means from it."""
        tree = tree._replace(
            leaf_value=tree.leaf_value * shrinkage,
            internal_value=tree.internal_value * shrinkage)
        if tree.leaf_const is not None:
            # linear leaves shrink intercept + coefficients with the
            # constant (the reference scales the whole leaf model)
            tree = tree._replace(leaf_const=tree.leaf_const * shrinkage,
                                 leaf_coeff=tree.leaf_coeff * shrinkage)
        tree = self._tree_output_transform(tree)
        if self.nan_policy == "none":
            return tree, None
        from ..robustness.numeric import clip_nonfinite, nonfinite_flag
        bl = nonfinite_flag(tree.leaf_value)
        if self.nan_policy == "clip":
            tree = tree._replace(
                leaf_value=clip_nonfinite(tree.leaf_value),
                internal_value=clip_nonfinite(tree.internal_value))
        return tree, bl

    def _leaf_contrib(self, leaf_ids, leaf_value):
        """Each training row's leaf value. Under a row-sharded mesh every
        shard looks up its own rows (``shard_map``): left to the
        partitioner, the blocked lookup's reshape and its loop over row
        blocks all-gather the leaf ids across devices, the one row-sized
        exchange the step ever made (the step compiled for four described
        v5e chips; PERF.md, PR 34)."""
        rows = self.pctx.row_sharding()
        if rows is None:
            return table_lookup(leaf_ids, leaf_value)
        return jax.shard_map(table_lookup, mesh=self.pctx.mesh,
                             in_specs=(rows.spec, P()), out_specs=rows.spec,
                             check_vma=False)(leaf_ids, leaf_value)

    def _tree_score_updates(self, score_k, valid_k, valid_Xb, tree,
                            leaf_ids, it):
        """Apply one (shrunk) tree to the train score and every valid
        score: ``(new_score_k, [new_valid_k...])``. Linear trees swap the
        constant-leaf table lookup for the per-row linear epilogue
        (ops/linear.linear_leaf_scores) on both paths."""
        with jax.named_scope("step.score_update"):
            if self.linear_tree:
                from ..ops.linear import linear_leaf_scores
                contrib = linear_leaf_scores(tree, leaf_ids, self.Xraw,
                                             self.Xmiss)
            else:
                contrib = self._leaf_contrib(leaf_ids, tree.leaf_value)
            new_score_k = self._score_update(score_k, contrib, it)
        new_valid_k = []
        for vi in range(len(valid_Xb)):
            with jax.named_scope("step.valid_update"):
                vleaf = leaves_from_binned(
                    tree, valid_Xb[vi], self.num_bins, self.missing_code,
                    self.default_bin,
                    use_categorical=self.spec.use_categorical)
                if self.linear_tree:
                    from ..ops.linear import linear_leaf_scores
                    vs = self.valid_sets[vi]
                    vcontrib = linear_leaf_scores(tree, vleaf, vs.Xraw,
                                                  vs.Xmiss)
                else:
                    vcontrib = table_lookup(vleaf, tree.leaf_value)
                new_valid_k.append(
                    self._score_update(valid_k[vi], vcontrib, it))
        return new_score_k, new_valid_k

    # device-array attributes captured by the training step; under
    # multi-host they must travel as jit ARGUMENTS (closing over arrays
    # spanning non-addressable devices is rejected), so the step rebinds
    # them onto self for the duration of the trace.
    _STEP_CONSTS = ("Xb", "label", "weight", "pad_mask", "feature_ok_base",
                    "is_cat", "num_bins", "missing_code", "default_bin",
                    "Xraw", "Xmiss")

    def _step_consts(self):
        consts = {a: getattr(self, a) for a in self._STEP_CONSTS}
        # linear_tree: per-valid raw slices ride in the consts pytree (the
        # step rebinds them like vs.Xb, so they travel as jit ARGUMENTS and
        # are never baked into the executable as constants)
        consts["valid_raw"] = tuple((vs.Xraw, vs.Xmiss)
                                    for vs in self.valid_sets) \
            if self.linear_tree else None
        return consts, tuple(vs.Xb for vs in self.valid_sets)

    def _make_step(self, custom_grads: bool = False, batch: int = 1,
                   donate_override: Optional[tuple] = None):
        assert not (custom_grads and batch > 1), \
            "custom gradients need a host round-trip per tree"
        spec = self.spec
        K = self.num_models
        comm = self.comm
        linear_tree = self.linear_tree    # static per booster

        sampled = self.samples_rows       # static: the mask is the
                                          # histogram's row set
        bundle = self.bundle              # EFB: native arm scans/routes in
                                          # bundle space end-to-end; legacy
                                          # tpu_efb_unpack unpacks before
                                          # the collective (grower.py)

        def grow_fn(X, g, h, inc, fok, iscat, nb, mc, db):
            return grow_tree(X, g, h, inc, fok, iscat, nb, mc, db, spec, comm,
                             bundle=bundle, sampled=sampled)

        grow = self.pctx.shard_grow(grow_fn)

        def step(consts, valid_Xb, score, valid_scores, bag_mask, key, it,
                 shrinkage, *grads):
            # Rebind the captured arrays to this trace's tracers so every
            # hook (_gradients/_sampling/RF/GOSS overrides) reads arguments,
            # not baked-in constants. Python-level state is restored after
            # tracing; compiled executions never run this body again.
            # So this line counts TRACES, at no cost per dispatch
            # (``_dispatch`` counts executables and records the signature
            # that caused each).
            self._step_traces += 1
            obs.inc("compile.step_traces")
            saved = {a: getattr(self, a) for a in self._STEP_CONSTS}
            saved_vXb = [vs.Xb for vs in self.valid_sets]
            saved_vraw = [(vs.Xraw, vs.Xmiss) for vs in self.valid_sets]
            for a in self._STEP_CONSTS:
                setattr(self, a, consts[a])
            for vs, xb in zip(self.valid_sets, valid_Xb):
                vs.Xb = xb
            if linear_tree:     # static: self.linear_tree, fixed per booster
                for vs, (xr, xm) in zip(self.valid_sets,
                                        consts["valid_raw"]):
                    vs.Xraw, vs.Xmiss = xr, xm
            try:
                if batch == 1:
                    return step_body(score, valid_scores, bag_mask, key, it,
                                     shrinkage, *grads)
                return batch_body(score, valid_scores, bag_mask, key, it,
                                  shrinkage)
            finally:
                for a, v in saved.items():
                    setattr(self, a, v)
                for vs, xb in zip(self.valid_sets, saved_vXb):
                    vs.Xb = xb
                for vs, (xr, xm) in zip(self.valid_sets, saved_vraw):
                    vs.Xraw, vs.Xmiss = xr, xm

        def batch_body(score, valid_scores, bag_mask, key, it, shrinkage):
            # tree_batch fusion: `batch` whole iterations under ONE lax.scan
            # — the carry (scores, bagging mask, device iteration counter)
            # stays in HBM between trees; per-iteration trees / grow records
            # (/ non-finite flags) stack along the leading batch axis. The
            # scan body IS step_body, so K=1 and K>1 run identical math per
            # iteration (bit-identity is pinned by tests/test_tree_batch.py).
            def scan_step(carry, _):
                score, valid_scores, bag_mask, it = carry
                outs = step_body(score, valid_scores, bag_mask, key, it,
                                 shrinkage)
                score, valid_scores, bag_mask = outs[0], outs[1], outs[2]
                it = outs[5]
                return (score, valid_scores, bag_mask, it), \
                    (outs[3], outs[4]) + tuple(outs[6:])
            (score, valid_scores, bag_mask, it), ys = jax.lax.scan(
                scan_step, (score, valid_scores, bag_mask, it), None,
                length=batch)
            return (score, valid_scores, bag_mask) + tuple(ys[:2]) + (it,) \
                + tuple(ys[2:])

        nan_policy = self.nan_policy
        if nan_policy != "none":
            from ..robustness.numeric import clip_nonfinite, nonfinite_flag

        def step_body(score, valid_scores, bag_mask, key, it, shrinkage, *grads):
            # key arrives RAW; folding by the device iteration counter here
            # reproduces the former host-side fold_in(rng, iter_) stream
            # exactly (fold_in is value-deterministic) with zero per-step
            # host->device transfers
            key = jax.random.fold_in(key, it)
            bad_g = bad_h = bad_leaf = None
            with jax.named_scope("step.gradients"):
                if custom_grads:
                    g, h = grads
                else:
                    g, h = self._gradients(score)
                if nan_policy != "none":
                    # detect BEFORE any sanitizing so every policy can
                    # report which of g/h/leaf went non-finite
                    bad_g, bad_h = nonfinite_flag(g), nonfinite_flag(h)
                    if nan_policy == "clip":
                        g, h = clip_nonfinite(g), clip_nonfinite(h)
            bkey, fkey = jax.random.split(jax.random.fold_in(key, 0))
            with jax.named_scope("step.sampling"):
                mask, g, h, sample = self._sampling(g, h, bag_mask, bkey, it)
            trees = []
            nleaves = []
            wave_stats = []
            new_scores = []
            new_valid = [list(vs) for vs in valid_scores] if valid_scores else []
            vXb = tuple(vs.Xb for vs in self.valid_sets)
            for k in range(K):
                with jax.named_scope("step.grow"):
                    fmask = self._feature_mask(fkey, k)
                    tree, leaf_ids, stats = grow(
                        self.Xb, g[k] * mask, h[k] * mask, mask, fmask,
                        self.is_cat, self.num_bins, self.missing_code,
                        self.default_bin)
                    if self.linear_tree:
                        # per-leaf ridge fit (ops/linear.py): same masked
                        # g/h the tree grew on, BEFORE shrinkage so the
                        # intercept and coefficients scale together
                        # (Tree::Shrinkage)
                        from ..ops.linear import fit_linear_leaves
                        tree = fit_linear_leaves(
                            tree, self.Xraw, self.Xmiss, leaf_ids,
                            g[k] * mask, h[k] * mask, mask, self.is_cat,
                            max_features=self.config.linear_max_features,
                            linear_lambda=self.config.linear_lambda,
                            chunk_rows=spec.chunk_rows,
                            max_steps=self._linear_max_steps)
                with jax.named_scope("step.score_update"):
                    tree, bl = self._shrink_transform_flag(tree, shrinkage)
                if bl is not None:
                    bad_leaf = bl if bad_leaf is None else (bad_leaf | bl)
                new_score_k, new_valid_k = self._tree_score_updates(
                    score[k], [new_valid[vi][k] for vi in range(len(vXb))],
                    vXb, tree, leaf_ids, it)
                new_scores.append(new_score_k)
                for vi in range(len(vXb)):
                    new_valid[vi][k] = new_valid_k[vi]
                trees.append(tree)
                nleaves.append(tree.num_leaves)
                wave_stats.append(stats)
            out_score = jnp.stack(new_scores)
            out_valid = tuple(tuple(v) for v in new_valid)
            record = GrowRecord(
                jnp.stack(nleaves),
                jax.tree.map(lambda *a: jnp.stack(a), *wave_stats), sample)
            if nan_policy == "none":
                return (out_score, out_valid, mask, tuple(trees),
                        record, it + 1)
            nf = jnp.stack([bad_g, bad_h, bad_leaf])
            if nan_policy in ("raise", "skip_iter"):
                # hardware-gate every output on the poison flag: a poisoned
                # iteration leaves scores/masks BIT-identical to their
                # pre-step values, so host-side recovery is pure bookkeeping
                # (pop the no-op iteration), never NaN arithmetic
                bad = jnp.any(nf)
                out_score = jnp.where(bad, score, out_score)
                out_valid = tuple(
                    tuple(jnp.where(bad, old_k, new_k)
                          for old_k, new_k in zip(old_vs, new_vs))
                    for old_vs, new_vs in zip(valid_scores, out_valid))
                mask = jnp.where(bad, bag_mask, mask)
            return (out_score, out_valid, mask, tuple(trees),
                    record, it + 1, nf)

        # donate the training-step carry (positions: score=2,
        # valid_scores=3, and under bagging bag_mask=4) — every one is
        # rebound to the step's outputs immediately after each dispatch, so
        # XLA updates in place instead of allocating + copying a second
        # [K, Npad] f32 array per step (42 MB at bench scale). bag_mask is
        # only donated when bagging resamples it (otherwise the step returns
        # pad_mask, which also travels as a non-donated constant). The
        # grower's per-tree leaf state and histogram cache live inside the
        # while_loop carry, which XLA already aliases in place. CPU ignores
        # donation with a warning, so gate it.
        # donate_override exists for the trace-contract tier
        # (analysis/contracts): the CPU gate would make the donation
        # contract vacuous on the dev box, so the contract compiles the
        # step with the TPU-style donate set forced on and checks the
        # aliases in the HLO header instead of trusting this branch.
        if donate_override is not None:
            donate = tuple(donate_override)
        else:
            donate = () if self.pctx.devices[0].platform == "cpu" else \
                ((2, 3, 4) if self.bagging_on else (2, 3))
        return jax.jit(step, donate_argnums=donate)

    def _place_step_scalars(self, shrinkage: float) -> None:
        """The step's device counter (first step / post-rollback resync) and
        its shrinkage, PLACED like the step's own outputs: an uncommitted
        scalar on the first call and the step's committed output on the
        second are two signatures, and the step compiled twice for them
        (333.7 + 327.4 s at 14.7M rows; PERF.md, PR 27)."""
        if self._iter_dev is None:
            self._iter_dev = self._put(np.asarray(self.iter_, np.int32))
        if self._shrink_cache[0] != shrinkage:
            self._shrink_cache = (shrinkage,
                                  self._put(np.asarray(shrinkage, np.float32)))

    def _dispatch_prep(self, shrinkage: float):
        """Shared pre-dispatch protocol of the K=1 and fused-batch paths:
        device-counter resync, on-device shrinkage cache, valid-score /
        step-constant assembly. ONE copy so the two dispatchers cannot
        drift."""
        self._place_step_scalars(shrinkage)
        valid_scores = tuple(tuple(vs.score[k] for k in range(self.num_models))
                             for vs in self.valid_sets)
        consts, valid_Xb = self._step_consts()
        return consts, valid_Xb, valid_scores

    def _dispatch(self, site: str, fn, args):
        """The jitted call itself, under the ``step.dispatch`` span (the
        ``launch`` part of the call's always-on record): an
        enqueue in steady state; trace and/or compile (or cache load) on a
        call whose signature jit has not seen. Two counters tell which:
        ``compile.step_traces`` moves when jax ran the step's Python body
        again, ``compile.step_executables`` when the jitted function's
        cache gained an entry (``_cache_size``, as RecompileGuard reads
        it): a new executable was compiled or loaded, WITH OR WITHOUT a
        retrace — arguments that only change from uncommitted to committed
        reuse the trace and still pay the compile. Either way the instant
        event ``compile.step_trace`` records the arguments' abstract
        signature and which of its leaves differ from this site's previous
        one: the answer to "what recompiled, and why"; and the call's own
        seconds go to ``compile.step_first_call_s``, split by the durations
        ``jax.monitoring`` reported inside it into ``compile.step_trace_s``,
        ``compile.step_lower_s``, ``compile.step_backend_s`` (compile or
        load) and ``compile.step_cache_load_s`` (the load alone)."""
        with obs.step_part("launch", site=site) as t0:
            before = (self._step_traces, fn._cache_size())
            with obs.compile_watch() as fired:
                outs = fn(*args)
            after = (self._step_traces, fn._cache_size())
            if after != before:
                self._record_new_executable(site, args, before, after,
                                            obs.clock() - t0, fired)
        return outs

    def _record_new_executable(self, site: str, args, before, after,
                               first_call_s: float, fired) -> None:
        """``_dispatch``'s record of a call that traced and/or compiled:
        the counters, the ``compile.step_trace`` event, the INFO line."""
        split = obs.compile_split(fired)
        reg = obs.get_registry()
        reg.counter("compile.step_first_call_s").inc(first_call_s)
        for kind, seconds in split.items():
            reg.counter(f"compile.step_{kind}_s").inc(seconds)
        obs.inc("compile.step_executables", after[1] - before[1])
        sig = _abstract_signature(args)
        last = self._trace_signatures.get(site, sig)
        changed = {k: f"{last.get(k)} -> {v}" for k, v in sig.items()
                   if last.get(k) != v}
        self._trace_signatures[site] = sig
        obs.event("compile.step_trace", site=site, traces=after[0],
                  retraced=after[0] != before[0],
                  executables=after[1], changed=changed, signature=sig,
                  first_call_s=round(first_call_s, 6),
                  **{f"{kind}_s": round(seconds, 6)
                     for kind, seconds in split.items()})
        if changed:
            Log.info("%s: a new executable (number %d of this step; "
                     "retraced: %s) because %s", site, after[1],
                     after[0] != before[0], changed)

    def _capture_step_cost(self, site: str, fn, args, batch: int) -> None:
        """Cost-report leg of the dispatch protocol (observability/costs.py,
        gated on ``costs.enabled()`` by the callers): lower+compile the SAME
        jitted step with the live arguments once per executable and publish
        FLOPs / bytes-accessed / argument+temp HBM. Compile-time only — no
        steady-state recompile, no host sync (``bench.py --smoke`` A/Bs the
        fused loop with capture on)."""
        obs_costs.capture_jit(
            site, fn, args,
            dims=dict(rows=int(self.num_data),
                      rows_padded=int(self.num_data_padded),
                      features=int(self.spec.num_features),
                      num_leaves=int(self.spec.num_leaves),
                      hist_slots=int(self.spec.hist_slots),
                      tree_batch=int(batch), num_models=int(self.num_models),
                      kernel=self.spec.hist_kernel,
                      strategy=self.pctx.strategy,
                      # gates the measured-collectives HLO scan (costs.py):
                      # serial steps never materialize the HLO text
                      n_devices=int(self.pctx.num_devices)))

    def _run_step(self, score, shrinkage: float, custom_gh=None):
        """Dispatch one compiled step against current state; returns new score
        and per-valid score tuples (device)."""
        site = "train_step.k1" + (".custom" if custom_gh is not None else "")
        with obs.step_part("prep"):
            if custom_gh is None:
                if self._step_fn is None:
                    self._step_fn = self._make_step()
                fn, extra = self._step_fn, ()
            else:
                if self._custom_step_fn is None:
                    self._custom_step_fn = self._make_step(custom_grads=True)
                fn, extra = self._custom_step_fn, custom_gh
            consts, valid_Xb, valid_scores = self._dispatch_prep(shrinkage)
            args = (consts, valid_Xb, score, valid_scores, self.bag_mask,
                    self._rng_key, self._iter_dev, self._shrink_cache[1],
                    *extra)
            if obs_costs.enabled():
                # compile-time cost report of THIS dispatch signature —
                # captured once per (site, executable), before the first
                # call so the AOT compile primes the persistent cache the
                # dispatch then hits
                self._capture_step_cost(site, fn, args, 1)
        outs = self._dispatch(site, fn, args)
        with obs.step_part("post"):
            nf = None
            if self.nan_policy != "none":
                (score, out_valid, self.bag_mask, trees, record,
                 self._iter_dev, nf) = outs
            else:
                (score, out_valid, self.bag_mask, trees, record,
                 self._iter_dev) = outs
            self.models.append(list(trees))
            self._grow_records.append(record)
            self.iter_ += 1
            self.mutations_ = getattr(self, "mutations_", 0) + 1
            if nf is not None:
                try:
                    self._apply_nan_policy(nf)
                except Exception:
                    # the pre-step buffers were DONATED to the step — rebind
                    # the (gated, bit-identical) outputs before propagating
                    # so the booster stays usable and checkpointable after
                    # the failure
                    self.score = score
                    for vi, vs in enumerate(self.valid_sets):
                        vs.score = jnp.stack(out_valid[vi])
                    raise
        return score, out_valid

    def _record_nan_event(self, what: str, iteration: int) -> None:
        """Telemetry leg of the nan_policy guard: per-policy counters plus
        an instant trace event per poisoned iteration — the chaos suite
        asserts these land in the JSONL stream (tests/test_chaos.py)."""
        reg = obs.get_registry()
        reg.counter("nan.events").inc()
        reg.counter({"clip": "nan.clipped", "raise": "nan.raised",
                     "skip_iter": "nan.skipped_iters"}.get(
                         self.nan_policy, "nan.other")).inc()
        obs.event("nan_policy", policy=self.nan_policy, what=what,
                  iteration=int(iteration))

    @allowed_host_sync("nan_policy guard: one 3-bool flag fetch per "
                       "iteration, only while the guard is enabled")
    def _apply_nan_policy(self, nf) -> bool:
        """Host-side leg of the non-finite guard: fetch the step's three
        detection flags and enforce self.nan_policy. Under raise/skip_iter
        the step already gated every array output to its pre-step value, so
        recovery here is pure bookkeeping. Returns True iff the iteration
        was dropped."""
        flags = np.asarray(nf)
        if not flags.any():
            self._consecutive_skips = 0
            return False
        from ..robustness.numeric import FLAG_NAMES, NonFiniteError
        what = ", ".join(n for n, f in zip(FLAG_NAMES, flags) if f)
        self._record_nan_event(what, self.iter_ - 1)
        if self.nan_policy == "clip":
            Log.warning("nan_policy=clip: non-finite %s at iteration %d "
                        "were sanitized (NaN->0, Inf->+/-cap)", what,
                        self.iter_ - 1)
            self._consecutive_skips = 0
            return False
        self._pop_last_iteration()
        if self.nan_policy == "raise":
            raise NonFiniteError(
                f"non-finite {what} detected at iteration {self.iter_} "
                f"(nan_policy=raise); booster state is rolled back to the "
                f"last clean iteration and remains checkpointable")
        self._consecutive_skips += 1
        Log.warning("nan_policy=skip_iter: dropped iteration %d "
                    "(non-finite %s); %d consecutive skip(s)", self.iter_,
                    what, self._consecutive_skips)
        if self._consecutive_skips >= 10:
            raise NonFiniteError(
                f"nan_policy=skip_iter: {self._consecutive_skips} "
                f"consecutive iterations produced non-finite {what} — the "
                f"poison is deterministic, aborting instead of spinning")
        return True

    def train_one_iter(self) -> None:
        # span nesting mirrors the fused path: one dispatch ("tree_batch",
        # k=1) holding one iteration — host-side bookkeeping only, no device
        # value is read (the recompile-free steady state is preserved)
        with obs.step_call(), obs.span("tree_batch", k=1), \
                obs.span("iteration", iteration=self.iter_):
            if self.residency == "stream":
                score, out_valid = self._run_streamed_step(
                    self._step_shrinkage())
            else:
                score, out_valid = self._run_step(self.score,
                                                  self._step_shrinkage())
            self.score = score
            for vi, vs in enumerate(self.valid_sets):
                vs.score = jnp.stack(out_valid[vi])

    def _step_shrinkage(self) -> float:
        """Hook: per-tree shrinkage (RF overrides to 1.0, rf.hpp:44-45)."""
        return self.config.learning_rate

    # ------------------------------------- streamed step (tpu_residency=stream)

    def _make_stream_fns(self) -> Dict:
        """Jitted legs of the streamed training step. The resident step is
        ONE jit; in stream mode the shard loop is host-driven, so the step
        splits at the grower boundary into ``pre`` (RNG fold + gradients +
        non-finite detection + bagging), ``prep`` (per-model masked grads +
        feature_fraction mask), ``shrink`` (shrinkage + output transform +
        leaf flag), and ``apply`` (train/valid score updates, nan gating,
        device iteration counter). Each leg traces through the SAME hook
        methods ``step_body`` uses, in the same order, so a streamed
        iteration is bit-identical to a resident one. All shapes are fixed
        — the whole set compiles once per booster (RecompileGuard-pinned in
        tests/test_stream.py)."""
        spec = self.spec
        K = self.num_models
        nan_policy = self.nan_policy
        if nan_policy != "none":
            from ..robustness.numeric import clip_nonfinite, nonfinite_flag

        def make_pre(custom: bool):
            def pre_body(score, bag_mask, key, it, *grads):
                key = jax.random.fold_in(key, it)
                if custom:
                    g, h = grads
                else:
                    g, h = self._gradients(score)
                bad = ()
                if nan_policy != "none":
                    bad_g, bad_h = nonfinite_flag(g), nonfinite_flag(h)
                    if nan_policy == "clip":
                        g, h = clip_nonfinite(g), clip_nonfinite(h)
                    bad = (bad_g, bad_h)
                bkey, fkey = jax.random.split(jax.random.fold_in(key, 0))
                mask, g, h, _ = self._sampling(g, h, bag_mask, bkey, it)
                return (g, h, mask, fkey) + bad
            return pre_body

        def prep_body(g, h, mask, fkey, k):
            return g[k] * mask, h[k] * mask, self._feature_mask(fkey, k)

        def shrink_body(tree, shrinkage):
            return self._shrink_transform_flag(tree, shrinkage)

        def apply_body(score, valid_scores, valid_Xb, bag_mask, mask,
                       trees, leaf_ids, it, flags):
            new_scores = []
            new_valid = [list(vs) for vs in valid_scores] if valid_scores \
                else []
            for k in range(K):
                new_score_k, new_valid_k = self._tree_score_updates(
                    score[k],
                    [new_valid[vi][k] for vi in range(len(valid_Xb))],
                    valid_Xb, trees[k], leaf_ids[k], it)
                new_scores.append(new_score_k)
                for vi in range(len(valid_Xb)):
                    new_valid[vi][k] = new_valid_k[vi]
            out_score = jnp.stack(new_scores)
            out_valid = tuple(tuple(v) for v in new_valid)
            nl = jnp.stack([t.num_leaves for t in trees])
            if nan_policy == "none":
                return out_score, out_valid, mask, nl, it + 1
            bad_g, bad_h, bad_leafs = flags
            bad_leaf = bad_leafs[0]
            for bl in bad_leafs[1:]:
                bad_leaf = bad_leaf | bl
            nf = jnp.stack([bad_g, bad_h, bad_leaf])
            if nan_policy in ("raise", "skip_iter"):
                # hardware-gate every output on the poison flag, exactly
                # like the resident step: a poisoned iteration leaves
                # scores/masks BIT-identical to their pre-step values
                bad = jnp.any(nf)
                out_score = jnp.where(bad, score, out_score)
                out_valid = tuple(
                    tuple(jnp.where(bad, old_k, new_k)
                          for old_k, new_k in zip(old_vs, new_vs))
                    for old_vs, new_vs in zip(valid_scores, out_valid))
                mask = jnp.where(bad, bag_mask, mask)
            return out_score, out_valid, mask, nl, it + 1, nf

        # donate the carried score/valid-scores (and, under bagging, the
        # previous mask) into apply — the streamed twin of _make_step's
        # donate_argnums, with the same rebind-immediately discipline
        donate = () if self.pctx.devices[0].platform == "cpu" else \
            ((0, 1, 3) if self.bagging_on else (0, 1))
        return dict(pre=jax.jit(make_pre(False)),
                    pre_custom=jax.jit(make_pre(True)),
                    prep=jax.jit(prep_body),
                    shrink=jax.jit(shrink_body),
                    apply=jax.jit(apply_body, donate_argnums=donate))

    def _run_streamed_step(self, shrinkage: float, custom_gh=None):
        """One streamed boosting iteration: pre -> per-model (prep ->
        StreamedGrower.grow over the shard prefetcher -> shrink) -> apply,
        with the SAME host bookkeeping contract as ``_run_step`` (models
        appended, counters advanced, then the nan policy fetch)."""
        # the same three parts as the resident step; the host drives the
        # shard loop, so every leg from ``pre`` to ``apply`` is the launch
        with obs.step_part("prep", streamed=True):
            if self._stream_fns is None:
                self._stream_fns = self._make_stream_fns()
            fns = self._stream_fns
            self._place_step_scalars(shrinkage)
            valid_scores = tuple(tuple(vs.score[k] for k in range(self.num_models))
                                 for vs in self.valid_sets)
            valid_Xb = tuple(vs.Xb for vs in self.valid_sets)
        with obs.step_part("launch", site="train_step.stream"):
            if custom_gh is not None:
                outs = fns["pre_custom"](self.score, self.bag_mask,
                                         self._rng_key, self._iter_dev,
                                         *custom_gh)
            else:
                outs = fns["pre"](self.score, self.bag_mask, self._rng_key,
                                  self._iter_dev)
            if self.nan_policy != "none":
                g, h, mask, fkey, bad_g, bad_h = outs
            else:
                g, h, mask, fkey = outs
                bad_g = bad_h = None
            trees, leaf_ids, bad_leafs = [], [], []
            for k in range(self.num_models):
                gk, hk, fmask = fns["prep"](g, h, mask, fkey, np.int32(k))
                tree_raw, lid = self._streamed_grower.grow(
                    self._stream, gk, hk, mask, fmask)
                tree, bl = fns["shrink"](tree_raw, self._shrink_cache[1])
                if bl is not None:
                    bad_leafs.append(bl)
                trees.append(tree)
                leaf_ids.append(lid)
            flags = ((bad_g, bad_h, tuple(bad_leafs))
                     if self.nan_policy != "none" else None)
            outs = fns["apply"](self.score, valid_scores, valid_Xb,
                                self.bag_mask, mask, tuple(trees),
                                tuple(leaf_ids), self._iter_dev, flags)
        with obs.step_part("post"):
            nf = None
            if self.nan_policy != "none":
                score, out_valid, self.bag_mask, nl, self._iter_dev, nf = outs
            else:
                score, out_valid, self.bag_mask, nl, self._iter_dev = outs
            self.models.append(list(trees))
            # the host drives a streamed tree's waves: no loop on the device,
            # so no record of one
            self._grow_records.append(GrowRecord(nl, None))
            self.iter_ += 1
            self.mutations_ = getattr(self, "mutations_", 0) + 1
            if nf is not None:
                try:
                    self._apply_nan_policy(nf)
                except Exception:
                    # the pre-step score/valid buffers were DONATED to apply —
                    # rebind the (gated, bit-identical) outputs before
                    # propagating, exactly like the resident path
                    self.score = score
                    for vi, vs in enumerate(self.valid_sets):
                        vs.score = jnp.stack(out_valid[vi])
                    raise
        return score, out_valid

    # --------------------------------------------- fused multi-tree dispatch

    def train_batch(self, n: int) -> None:
        """Run ``n`` boosting iterations in ONE jit dispatch (tree_batch).

        Equivalent to ``n`` calls of :meth:`train_one_iter` (bit-identical —
        the scan body is the same ``step_body``), but score updates, tree
        growth, and leaf application never leave HBM between trees and the
        host pays dispatch + bookkeeping cost once per batch. Metric eval /
        callbacks happen at the caller's batch boundaries (engine.py)."""
        if n <= 1:
            return self.train_one_iter()
        if self.residency == "stream":
            # tree_batch is forced to 1 at construction (the shard loop is
            # host-driven); a direct caller still gets the equivalent
            # semantics, unfused
            for _ in range(n):
                self.train_one_iter()
            return
        # the fused scan is ONE dispatch: there is no host boundary between
        # its iterations, so no per-iteration span (the device trace's
        # ``step.*`` scopes show them)
        with obs.step_call(), \
                obs.span("tree_batch", k=n, iteration=self.iter_):
            self._run_fused_batch(n)

    def _run_fused_batch(self, n: int) -> None:
        site = f"train_step.k{n}"
        with obs.step_part("prep"):
            fn = self._batch_step_fns.get(n)
            if fn is None:
                fn = self._make_step(batch=n)
                self._batch_step_fns[n] = fn
            consts, valid_Xb, valid_scores = self._dispatch_prep(
                self._step_shrinkage())
            args = (consts, valid_Xb, self.score, valid_scores,
                    self.bag_mask, self._rng_key, self._iter_dev,
                    self._shrink_cache[1])
            if obs_costs.enabled():
                self._capture_step_cost(site, fn, args, n)
        outs = self._dispatch(site, fn, args)
        with obs.step_part("post"):
            nf = None
            if self.nan_policy != "none":
                (score, out_valid, self.bag_mask, trees, records,
                 self._iter_dev, nf) = outs
            else:
                (score, out_valid, self.bag_mask, trees, records,
                 self._iter_dev) = outs
            # per-iteration bookkeeping from the stacked batch outputs: lazy
            # device-side slices (no host sync), so checkpoints / rollback /
            # finalize keep their list-of-iterations contract unchanged
            base_iter = self.iter_
            base_len = len(self.models)
            for i in range(n):
                self.models.append([
                    jax.tree.map(lambda x, i=i: x[i], tk) for tk in trees])
                self._grow_records.append(
                    jax.tree.map(lambda x, i=i: x[i], records))
            self.iter_ += n
            self.mutations_ = getattr(self, "mutations_", 0) + n
            self.score = score
            for vi, vs in enumerate(self.valid_sets):
                vs.score = jnp.stack(out_valid[vi])
            if nf is not None:
                self._apply_nan_policy_batch(nf, base_iter, base_len, n)

    @allowed_host_sync("nan_policy guard: one [K, 3] flag fetch per fused "
                       "batch, only while the guard is enabled")
    def _apply_nan_policy_batch(self, nf, base_iter: int, base_len: int,
                                n: int) -> None:
        """Batch-boundary leg of the non-finite guard under tree_batch>1:
        fetch the stacked per-iteration flags once and enforce the policy
        per inner iteration. A poisoned inner step was already hardware-
        gated to a bit-identical no-op inside the scan, so recovery drops
        its (zero-contribution) bookkeeping entry. Unlike the K=1 path, a
        skipped iteration's RNG draw is consumed — ``iter_`` and the device
        counter keep advancing through the batch (so no same-key retry
        spin), which means ``iter_`` counts attempted steps and can exceed
        ``len(models)`` after drops."""
        flags = np.asarray(nf)                              # [n, 3]
        if not flags.any():
            self._consecutive_skips = 0
            return
        from ..robustness.numeric import FLAG_NAMES, NonFiniteError

        def _what(i):
            return ", ".join(nm for nm, f in zip(FLAG_NAMES, flags[i]) if f)

        for i in np.nonzero(flags.any(axis=1))[0]:
            self._record_nan_event(_what(int(i)), base_iter + int(i))
        if self.nan_policy == "clip":
            for i in np.nonzero(flags.any(axis=1))[0]:
                Log.warning("nan_policy=clip: non-finite %s at iteration %d "
                            "were sanitized (NaN->0, Inf->+/-cap)",
                            _what(i), base_iter + int(i))
            self._consecutive_skips = 0
            return
        if self.nan_policy == "raise":
            i = int(np.nonzero(flags.any(axis=1))[0][0])
            what = _what(i)
            # roll the batch back to the last clean iteration: trailing
            # CLEAN trees are subtracted (they trained from the gated carry
            # and are valid, but "raise" promises state at the failure
            # point); trailing POISONED entries were gated no-ops whose
            # trees may hold non-finite leaf values — subtracting those
            # would NaN-poison the "rolled back" scores, so they are popped
            # without arithmetic. Finally the first poisoned entry drops.
            for j in range(n - 1, i, -1):
                if flags[j].any():
                    self._pop_last_iteration()
                else:
                    self.rollback_one_iter()
            self._pop_last_iteration()
            raise NonFiniteError(
                f"non-finite {what} detected at iteration {base_iter + i} "
                f"(nan_policy=raise, tree_batch={n}); booster state is "
                f"rolled back to the last clean iteration and remains "
                f"checkpointable")
        # skip_iter: drop poisoned entries (their steps were gated no-ops,
        # so the carried scores already exclude them); iter_ / the device
        # counter stay advanced so the RNG stream never reuses a key
        for i in sorted(np.nonzero(flags.any(axis=1))[0], reverse=True):
            Log.warning("nan_policy=skip_iter: dropped iteration %d "
                        "(non-finite %s)", base_iter + int(i), _what(i))
            del self.models[base_len + int(i)]
            del self._grow_records[base_len + int(i)]
        self.mutations_ = getattr(self, "mutations_", 0) + 1
        # consecutive-skip accounting walks the batch in order
        for i in range(n):
            if flags[i].any():
                self._consecutive_skips += 1
                if self._consecutive_skips >= 10:
                    raise NonFiniteError(
                        f"nan_policy=skip_iter: {self._consecutive_skips} "
                        f"consecutive iterations produced non-finite values "
                        f"— the poison is deterministic, aborting instead "
                        f"of spinning")
            else:
                self._consecutive_skips = 0

    # ---------------------------------------------------- custom objective

    def train_one_iter_custom(self, fobj) -> None:
        """One iteration with user-supplied gradients (reference
        LGBM_BoosterUpdateOneIterCustom, c_api.cpp:892): fobj(preds, dataset)
        -> (grad, hess) as numpy [K*N] in class-major order."""
        K, Npad, N = self.num_models, self.num_data_padded, self.num_data
        if self._block_counts is not None:
            Log.fatal("custom objectives are not supported with "
                      "is_pre_partition (host gradients need the full score "
                      "vector on every process)")
        with obs.step_call(), obs.span("tree_batch", k=1, custom_fobj=True), \
                obs.span("iteration", iteration=self.iter_):
            # the score's fetch, the user's objective and the gradients'
            # upload are this call's preparation too
            with obs.step_part("prep", custom_fobj=True):
                preds = self._fetch(self.score)[:, :N].reshape(-1)
                grad, hess = fobj(preds, self.train_set)
                g = np.zeros((K, Npad), np.float32)
                h = np.zeros((K, Npad), np.float32)
                g[:, :N] = np.asarray(grad, np.float32).reshape(K, N)
                h[:, :N] = np.asarray(hess, np.float32).reshape(K, N)
                custom_gh = (self._put(g, "rows1"), self._put(h, "rows1"))
            if self.residency == "stream":
                score, out_valid = self._run_streamed_step(
                    self.config.learning_rate, custom_gh=custom_gh)
            else:
                score, out_valid = self._run_step(
                    self.score, self.config.learning_rate,
                    custom_gh=custom_gh)
            self.score = score
            for vi, vs in enumerate(self.valid_sets):
                vs.score = jnp.stack(out_valid[vi])

    def add_base_score(self, raw_scores: np.ndarray,
                       valid_raw: Optional[List[np.ndarray]] = None) -> None:
        """Seed scores with a loaded model's predictions — continued training
        (reference: input_model re-predicted onto the data via PredictFunction,
        application.cpp:90-93 / boosting.h:281-284)."""
        K, Npad, N = self.num_models, self.num_data_padded, self.num_data
        add = np.zeros((K, Npad), np.float32)
        add[:, :N] = np.asarray(raw_scores, np.float32).reshape(K, N)
        self.score = self.score + self._put(add, "rows1")
        for vi, vs in enumerate(self.valid_sets):
            if valid_raw is not None and vi < len(valid_raw):
                vs.score = vs.score + self._put(
                    np.asarray(valid_raw[vi], np.float32).reshape(K, vs.num_data))

    def rollback_one_iter(self) -> None:
        """Reference GBDT::RollbackOneIter (gbdt.cpp:475-491): pop the last
        iteration's trees and subtract their contribution from all scores."""
        if self.average_output:
            Log.fatal("rollback_one_iter is not supported for rf boosting "
                      "(scores are running averages, not additive)")
        if self.residency == "stream":
            # subtracting a tree's contribution replays leaves_from_binned
            # over the full resident code matrix — which stream mode never
            # materializes. The nan_policy=raise path does not need it
            # (streamed steps gate their outputs before committing).
            Log.fatal("rollback_one_iter is not supported with "
                      "tpu_residency=stream (no resident code matrix to "
                      "replay leaf assignments from)")
        if not self.models:
            return
        trees = self.models.pop()
        self._grow_records.pop()
        self.iter_ -= 1
        self.mutations_ = getattr(self, "mutations_", 0) + 1
        self._iter_dev = None           # device counter resyncs next step
        score = self.score
        new_scores = []
        for k, tree in enumerate(trees):
            leaves = leaves_from_binned(tree, self.Xb, self.num_bins,
                                        self.missing_code, self.default_bin,
                                        bundle=self.bundle)
            if self.linear_tree:
                # subtract the SAME per-row linear output the step added
                from ..ops.linear import linear_leaf_scores
                contrib = linear_leaf_scores(tree, leaves, self.Xraw,
                                             self.Xmiss)
            else:
                contrib = tree.leaf_value[leaves]
            new_scores.append(score[k] - contrib)
            for vs in self.valid_sets:
                vleaves = leaves_from_binned(tree, vs.Xb, self.num_bins,
                                             self.missing_code, self.default_bin)
                if self.linear_tree:
                    from ..ops.linear import linear_leaf_scores
                    vcontrib = linear_leaf_scores(tree, vleaves, vs.Xraw,
                                                  vs.Xmiss)
                else:
                    vcontrib = tree.leaf_value[vleaves]
                vs.score = vs.score.at[k].add(-vcontrib)
        self.score = jnp.stack(new_scores)

    def reset_config(self, new_config: Config) -> None:
        """Apply per-iteration tunable parameters (reference
        LGBM_BoosterResetParameter). Structural parameters (num_leaves,
        max_bin, ...) are compiled into the grower and cannot change here;
        learning_rate & bagging settings take effect next iteration."""
        old = self.config
        self.config = new_config
        self.bagging_on = (new_config.bagging_freq > 0
                           and new_config.bagging_fraction < 1.0)
        if self.bagging_on and self.bag_mask is self.pad_mask:
            # bagging enabled mid-training: the carried mask is about to be
            # DONATED by the retraced step, so it must stop aliasing
            # pad_mask (the same invariant __init__ establishes)
            self.bag_mask = self.pad_mask + 0
        # Hyperparameters baked into GrowerSpec as trace-time constants take
        # effect by rebuilding the spec and dropping the cached executable.
        spec_changes = {}
        for field, attr in (
                ("lambda_l1", "lambda_l1"), ("lambda_l2", "lambda_l2"),
                ("min_gain_to_split", "min_gain_to_split"),
                ("cat_smooth", "cat_smooth"), ("cat_l2", "cat_l2"),
                ("max_cat_threshold", "max_cat_threshold"),
                ("max_cat_to_onehot", "max_cat_to_onehot")):
            if getattr(old, attr) != getattr(new_config, attr):
                spec_changes[field] = getattr(new_config, attr)
        if old.min_data_in_leaf != new_config.min_data_in_leaf:
            spec_changes["min_data_in_leaf"] = float(new_config.min_data_in_leaf)
        if old.min_sum_hessian_in_leaf != new_config.min_sum_hessian_in_leaf:
            spec_changes["min_sum_hessian_in_leaf"] = new_config.min_sum_hessian_in_leaf
        if old.min_data_per_group != new_config.min_data_per_group:
            spec_changes["min_data_per_group"] = float(new_config.min_data_per_group)
        retrace = bool(spec_changes)
        if old.linear_tree != new_config.linear_tree:
            # structural: the raw slice placement and every score-update
            # epilogue are decided at construction
            Log.fatal("linear_tree cannot change via reset_parameter "
                      "(rebuild the Booster)")
        if (old.linear_lambda != new_config.linear_lambda
                or old.linear_max_features != new_config.linear_max_features):
            retrace = True
        if spec_changes:
            import dataclasses
            self.spec = dataclasses.replace(self.spec, **spec_changes)
        # bagging fraction/freq are also compiled-in constants (learning_rate
        # is a traced argument — per-iteration schedules must not re-trace)
        if (old.bagging_freq != new_config.bagging_freq
                or old.bagging_fraction != new_config.bagging_fraction
                or old.feature_fraction != new_config.feature_fraction):
            retrace = True
        if old.nan_policy != new_config.nan_policy:
            # the guard is a trace-time constant: toggling it changes the
            # step program (and its output arity)
            self.nan_policy = new_config.nan_policy
            retrace = True
        if old.feature_fraction != new_config.feature_fraction:
            F = self.train_set.num_features
            self.n_feature_sample = max(
                1, int(round(new_config.feature_fraction * F)))
            self.use_feature_fraction = (new_config.feature_fraction < 1.0
                                         and self.n_feature_sample < F)
        if retrace:
            self._step_fn = None
            self._custom_step_fn = None
            self._batch_step_fns = {}
            self._stream_fns = None

    def _pop_last_iteration(self) -> None:
        """Drop the last appended iteration's bookkeeping WITHOUT score
        arithmetic — for iterations whose contribution never reached the
        scores (the no-splits pop; a nan_policy-gated no-op step). Contrast
        rollback_one_iter, which also subtracts the trees' contribution."""
        self.models.pop()
        self._grow_records.pop()
        self.iter_ -= 1
        self.mutations_ = getattr(self, "mutations_", 0) + 1
        self._iter_dev = None           # device counter resyncs next step

    def _check_no_splits(self) -> bool:
        """Reference gbdt.cpp:465-471: pop the no-split iteration(s) and stop
        when no tree could split. Checked at eval/batch boundaries, so ALL
        trailing degenerate iterations are popped — under tree_batch>1 (or
        metric_freq>1) several zero-value single-leaf trees can accumulate
        between checks."""
        popped = False
        while self._grow_records and \
                (np.asarray(self._grow_records[-1].num_leaves) <= 1).all():
            self._pop_last_iteration()
            popped = True
        if popped:
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements.")
        return popped

    # ------------------------------------------------------------------- eval

    def _fetch(self, arr) -> np.ndarray:
        """Device->host fetch that works for row-sharded arrays under
        multi-host execution (reassembles the global value on every process
        — the analog of the reference's metric eval running on each rank's
        local rows + allreduce; here metrics are computed on the full vector)."""
        if self.pctx.multi_process and not arr.is_fully_replicated:
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
        return np.asarray(arr)

    def eval_all(self, force_training=False, only=None
                 ) -> List[Tuple[str, str, float, bool]]:
        """only=<dataset name>: evaluate just that dataset (single-dataset
        entry points must not pay for every attached valid set)."""
        # always on: host seconds of each evaluation, in ``eval.host_s``
        with obs.timed_span(
                "eval", obs.get_registry().summary("eval.host_s").observe,
                only=only):
            return self._eval_all(force_training, only)

    def _eval_all(self, force_training=False, only=None
                  ) -> List[Tuple[str, str, float, bool]]:
        """Metric evaluation with a DEVICE scalar path for the pointwise
        family: the weighted-average loss reduces on device and only one
        scalar per metric crosses to the host (a full-score fetch per eval
        would be the next bottleneck). Rank/AUC/
        multiclass metrics still fetch the converted scores."""
        from ..metrics import _PointwiseRegressionMetric
        out: List = []
        pending: List[Tuple[int, object]] = []   # (out index, device scalar)

        def eval_dataset(dname, metrics, score_dev, label_dev, weight_dev,
                         mask_dev, fetch_conv):
            conv_dev = None
            conv_host = None
            for m in metrics:
                use_dev = (isinstance(m, _PointwiseRegressionMetric)
                           and self.num_models == 1)
                if use_dev:
                    if conv_dev is None:
                        conv_dev = self._convert(score_dev)
                    loss = m.loss(conv_dev[0], label_dev)
                    if weight_dev is None and mask_dev is None:
                        val = jnp.mean(loss)
                    else:
                        w = mask_dev if weight_dev is None else (
                            weight_dev if mask_dev is None
                            else weight_dev * mask_dev)
                        val = jnp.sum(loss * w) / jnp.sum(w)
                    out.append([dname, m.name, None, m.is_higher_better, m])
                    pending.append((len(out) - 1, val))
                else:
                    if conv_host is None:
                        conv_host = fetch_conv()
                    for name, value, hib in m.eval(conv_host):
                        out.append([dname, name, value, hib, None])

        if (self.config.is_training_metric or force_training) \
                and self.train_metrics and only in (None, "training"):
            eval_dataset(
                "training", self.train_metrics, self.score, self.label,
                self.weight, self.pad_mask,
                lambda: self._fetch(self._convert(self.score))[:, self._real_rows()])
        for vs in self.valid_sets:
            if only is not None and vs.name != only:
                continue
            if not hasattr(vs, "label_dev"):
                vs.label_dev = self._put(
                    np.asarray(vs.metadata.label, np.float32))
                w = vs.metadata.weight
                vs.weight_dev = None if w is None else self._put(
                    np.asarray(w, np.float32))
            eval_dataset(
                vs.name, vs.metrics, vs.score, vs.label_dev, vs.weight_dev,
                None, lambda vs=vs: self._fetch(self._convert(vs.score)))

        if pending:
            fetched = jax.device_get([v for (_i, v) in pending])
            for (i, _v), raw in zip(pending, fetched):
                m = out[i][4]
                out[i][2] = m.transform(float(raw))
        return [(d, n, v, h) for (d, n, v, h, _m) in out]

    def _convert(self, score):
        if self.objective is None or self.average_output:
            # RF scores are already averages of converted outputs (rf.hpp)
            return score
        return self.objective.convert_output(score)

    # ------------------------------------- checkpoint (robustness/checkpoint)

    @allowed_host_sync("checkpoint snapshot: full training-state fetch at an "
                       "iteration boundary, on demand only")
    def checkpoint_state(self) -> Dict:
        """Every array/counter the training step reads or writes, as host
        values (the ``state`` field of a checkpoint payload): raw scores,
        the carried bagging mask, the raw RNG key, the device forest
        (TreeArrays pytrees), per-iteration leaf counts, and the iteration/
        mutation counters. ``restore_checkpoint_state`` replays them so
        continued training is bit-identical to a never-interrupted run."""
        return {
            "iter": int(self.iter_),
            "data_fingerprint": self._data_fingerprint,
            "mutations": int(getattr(self, "mutations_", 0)),
            "consecutive_skips": int(self._consecutive_skips),
            "num_data": int(self.num_data),
            "num_data_padded": int(self.num_data_padded),
            "num_models": int(self.num_models),
            # mesh provenance: restore rejects a device-count change loudly
            # (or re-shards deliberately under tpu_reshard_on_resume) —
            # sharded state must never produce a silent shape error
            "n_devices": int(self.pctx.num_devices),
            "tree_learner": self.pctx.strategy,
            "block_layout": (None if self._block_counts is None
                             else list(self._block_counts)),
            "init_score_value": float(self.init_score_value),
            "score": np.asarray(self._fetch(self.score), np.float32),
            "bag_mask": np.asarray(self._fetch(self.bag_mask), np.float32),
            "rng_key": np.asarray(self._rng_key),
            "models": jax.device_get(self.models),
            "num_leaves": jax.device_get(
                [r.num_leaves for r in self._grow_records]),
            "valid_scores": {vs.name: np.asarray(vs.score)
                             for vs in self.valid_sets},
            "best_iteration": int(getattr(self, "best_iteration", 0)),
        }

    def restore_checkpoint_state(self, state: Dict) -> None:
        """Inverse of ``checkpoint_state``: replay a snapshot into this
        booster. Shape mismatches fail loudly. Restored arrays are placed
        with the same sharding kinds construction used, so an
        already-compiled step keeps hitting its executable — resume costs
        the normal first-step compile and nothing more (RecompileGuard-
        verified in ``bench.py --smoke``).

        Device-count changes are checked FIRST: a snapshot written on a
        different mesh is rejected loudly (the padded row layout, and under
        pre-partition the block layout, are functions of the device count —
        letting it through would surface as an opaque shape error). Setting
        ``tpu_reshard_on_resume=true`` re-shards deliberately instead: the
        training state is global-semantics (scores/masks in global row
        order, trees replicated), so the padded rows are re-laid-out onto
        this booster's mesh. Pre-partitioned snapshots never re-shard."""
        saved_d = state.get("n_devices")
        reshard = (saved_d is not None
                   and int(saved_d) != int(self.pctx.num_devices))
        if reshard:
            if not getattr(self.config, "tpu_reshard_on_resume", False):
                Log.fatal(
                    "checkpoint/mesh mismatch: the snapshot was written on "
                    "%d device(s) (tree_learner=%s) but this booster runs "
                    "on %d (%s) — sharded training state does not resume "
                    "across device counts. Rerun on the original mesh, or "
                    "set tpu_reshard_on_resume=true to re-shard the global "
                    "state deliberately", int(saved_d),
                    state.get("tree_learner", "?"), self.pctx.num_devices,
                    self.pctx.strategy)
            if state.get("block_layout") or self._block_counts is not None:
                Log.fatal(
                    "tpu_reshard_on_resume: pre-partitioned snapshots hold "
                    "per-process row blocks and cannot re-shard — resume on "
                    "the original process count")
            Log.warning("tpu_reshard_on_resume: re-sharding checkpoint "
                        "state written on %d device(s) onto %d (%s)",
                        int(saved_d), self.pctx.num_devices,
                        self.pctx.strategy)
        saved_tl = state.get("tree_learner")
        if saved_tl is not None and saved_tl != self.pctx.strategy \
                and not reshard:
            # as loud as the device-count guard above: a strategy swap at
            # the SAME device count changes what the carried row state
            # means (row-sharded vs replicated scores/masks) — never
            # silently reinterpretable. Only an authorized reshard (device
            # count changed + tpu_reshard_on_resume) may re-resolve the
            # strategy, e.g. data -> serial when a gang shrinks to one
            # device.
            Log.fatal(
                "checkpoint/learner mismatch: the snapshot was written "
                "under tree_learner=%s but this booster runs %s on the "
                "same device count — resume needs the same tree_learner "
                "(a strategy change is only honored through an elastic "
                "reshard: device count change + tpu_reshard_on_resume=true)",
                saved_tl, self.pctx.strategy)
        shape_checks = [("num_data", self.num_data),
                        ("num_models", self.num_models)]
        if not reshard:
            shape_checks.append(("num_data_padded", self.num_data_padded))
        for name, mine in shape_checks:
            if int(state[name]) != int(mine):
                Log.fatal("checkpoint/booster mismatch: %s is %d in the "
                          "snapshot but %d here — resume needs the same "
                          "dataset and training config", name,
                          int(state[name]), int(mine))
        fp = state.get("data_fingerprint")
        if fp and fp != self._data_fingerprint:
            Log.fatal("checkpoint/dataset mismatch: the snapshot was written "
                      "against different training data (binned-code/label "
                      "fingerprint differs) — a shape-compatible but "
                      "different dataset would silently corrupt the resumed "
                      "model")

        def _relayout(arr):
            # deliberate re-shard: the saved padded layout ([..., Npad_old],
            # real rows at the head — block layouts were rejected above) is
            # re-laid-out onto this booster's padding. Padding positions
            # carry no training signal (gradients are pad-masked; scores of
            # padding rows never reach metrics), so a zero refill is exact.
            arr = np.asarray(arr, np.float32)
            if not reshard or arr.shape[-1] == self.num_data_padded:
                return arr
            real = arr[..., : self.num_data]
            if real.ndim == 1:
                return self._row_layout(real)
            return np.stack([self._row_layout(r) for r in real])

        self.score = self._put(_relayout(state["score"]), "rows1")
        self.bag_mask = self._put(_relayout(state["bag_mask"]), "rows")
        self._rng_key = self._put(np.asarray(state["rng_key"]))
        self.models = [[jax.tree.map(self._put, t) for t in it_trees]
                       for it_trees in state["models"]]
        # restored iterations were grown (and their waves counted) by the
        # run that wrote the snapshot: leaf counts only
        self._grow_records = [GrowRecord(self._put(nl), None)
                              for nl in state["num_leaves"]]
        self.iter_ = int(state["iter"])
        # restored iterations were trained (and counted) by the run that
        # wrote the snapshot — telemetry must only count what THIS run adds
        self._telemetry_iters_base = len(self.models)
        self.mutations_ = int(state["mutations"])
        self._consecutive_skips = int(state.get("consecutive_skips", 0))
        self.init_score_value = float(state["init_score_value"])
        self.best_iteration = int(state.get("best_iteration", 0))
        self._iter_dev = None           # device counter resyncs next step
        self._shrink_cache = (None, None)
        restored = state.get("valid_scores", {})
        for vs in self.valid_sets:
            if vs.name in restored:
                vs.score = self._put(
                    np.asarray(restored[vs.name], np.float32))
            else:
                Log.warning("checkpoint has no saved scores for valid set "
                            "%r — its eval scores restart from the initial "
                            "model", vs.name)

    # -------------------------------------------------------------- telemetry

    def _fetch_records(self, records: List[GrowRecord]) -> List[GrowRecord]:
        """Grow records as host values. Single process: one ``device_get``
        (callers batch it with the trees). Multi-host: the counters are
        one row per DEVICE, so each process reads the rows of its own
        devices and publishes its own shards' maximum."""
        if not self.pctx.multi_process:
            return jax.device_get(records)

        def local_rows(a):          # [K, D, ...] -> [K, D_local, ...]
            return np.concatenate([np.asarray(sh.data)
                                   for sh in a.addressable_shards], axis=1)
        return [GrowRecord(np.asarray(r.num_leaves),
                           None if r.stats is None
                           else jax.tree.map(local_rows, r.stats),
                           None if r.sample is None
                           else jax.tree.map(np.asarray, r.sample))
                for r in records]

    def _publish_grow_records(self, host_records: List[GrowRecord]) -> None:
        """Publish what the iterations above the high-water mark counted
        about themselves (``host_records``: their fetched records, in
        order) to the process-wide registry: trees trained, leaves per
        tree, and — per tree, in tree order, from the wave loop's own
        record (grower.WaveStats / wave_totals), nothing modelled —
        ``grow.waves``, ``grow.hist_rows_touched``, ``grow.hist_rows_active``,
        ``grow.rows_split``, ``grow.compact_passes``, ``grow.stream_passes``,
        ``grow.hist_chunks`` (chunk matmuls the passes ran),
        ``grow.hist_acc_bytes`` (accumulator bytes the passes read and
        wrote: every chunk folds into it once, a chunk of a one-leaf wave
        into the one-leaf form's own), ``grow.one_leaf_passes`` and
        ``grow.hist_rows_one_leaf`` (the waves that took the one-leaf form
        of the chunk matmul and the rows they touched; only where the table
        has that form), ``grow.scan_slots`` and
        ``grow.scan_slots_pending``, and the counters ``rows.routed`` and
        ``hist.mxu_flops`` /
        ``hist.floor_flops``; from a step that draws a row sample also
        ``sample.rows_top``, ``sample.rows_other``, ``sample.rows_in`` (the
        step's own count of its sample, ``SampleStats``). Under a
        row-sharded mesh every ``grow.*`` row count is the pace-setting
        shard's (per-wave maximum over devices): compare with the rows of
        ONE device."""
        self._telemetry_iters_base = len(self.models)
        if not host_records:
            return
        reg = obs.get_registry()
        reg.counter("trees.trained").inc(len(host_records) * self.num_models)
        leaf_hist = reg.histogram("tree.leaves")
        for rec in host_records:
            for leaves in np.asarray(rec.num_leaves).reshape(-1):
                leaf_hist.observe(int(leaves))
            # a sampling step's own count of its sample, one entry a tree
            # (the K trees of a multiclass iteration share one sample)
            if rec.sample is not None:
                for name, rows in rec.sample._asdict().items():
                    for _ in range(self.num_models):
                        reg.summary("sample." + name).observe(int(rows))
        counted = [rec.stats for rec in host_records if rec.stats is not None]
        if not counted:
            return
        spec = self.spec
        n_dev = self.pctx.num_devices
        rows = self.num_data_padded // (
            n_dev if self.pctx.strategy in ("data", "voting") else 1)
        # a compacted pass runs whole chunks of its kernel's own size
        chunk = min(spec.chunk_rows, 512) \
            if spec.hist_kernel in ("pallas", "mixed") else spec.chunk_rows
        # MACs per histogrammed row: every code of the device's feature
        # block against every (padded) bin, into S slots x ch weight
        # channels (the kernel's formulation) or into the 3 channels no
        # histogram GBDT can avoid (the floor)
        cells = (self.Xb.shape[1]
                 // (n_dev if self.pctx.strategy == "feature" else 1)) \
            * (spec.hist_bins or spec.num_bins_padded)
        ch = num_channels(spec.hist_f64)
        for stats in counted:
            for k in range(self.num_models):
                t = wave_totals(jax.tree.map(lambda a, k=k: a[k], stats),
                                rows, chunk, spec.hist_slots)
                for name in ("waves", "hist_rows_touched", "hist_rows_active",
                             "rows_split", "compact_passes", "stream_passes",
                             "scan_slots", "scan_slots_pending",
                             "one_leaf_passes", "hist_rows_one_leaf"):
                    if t[name] is not None:
                        reg.summary("grow." + name).observe(t[name])
                # the Pallas kernel keeps its accumulator in VMEM: its
                # passes are not counted here. A chunk of a one-leaf wave
                # folds into the one-leaf form's own accumulator
                if spec.hist_kernel == "xla":
                    one_leaf = t["hist_chunks_one_leaf"] or 0
                    reg.summary("grow.hist_chunks").observe(t["hist_chunks"])
                    reg.summary("grow.hist_acc_bytes").observe(
                        2 * ((t["hist_chunks"] - one_leaf) * self._hist_acc_bytes
                             + one_leaf * self._hist_acc_bytes_one_leaf))
                if self._comm_bytes_per_wave:
                    moved = tree_collective_bytes(self._comm_bytes_per_wave,
                                                  t["waves"])
                    for cname, nbytes in moved["bytes"].items():
                        reg.summary("comm.bytes." + cname).observe(nbytes)
                    reg.summary("comm.collectives_per_tree").observe(
                        moved["collectives"])
                for d, (streamed, compacted) in enumerate(t["shard_passes"]):
                    reg.summary(f"grow.stream_passes.{d}").observe(streamed)
                    reg.summary(f"grow.compact_passes.{d}").observe(compacted)
                reg.counter("rows.routed").inc(t["rows_routed"])
                # a row of a one-leaf pass: each feature group's [G*bins_hi,
                # 128] product, one MAC a cell of that form's f32 accumulator
                one_leaf_rows = t["hist_rows_one_leaf"] or 0
                reg.counter("hist.mxu_flops").inc(
                    2 * ((t["hist_rows_touched"] - one_leaf_rows)
                         * cells * spec.hist_slots * ch
                         + one_leaf_rows * (self._hist_acc_bytes_one_leaf // 4)))
                reg.counter("hist.floor_flops").inc(
                    2 * t["hist_rows_touched"] * cells * 3)

    @allowed_host_sync("telemetry flush: one fetch of the iterations' small "
                       "grow records at the end of a training run, beside "
                       "the tree fetch that follows it")
    def publish_telemetry(self) -> None:
        """Flush this booster's per-run training facts into the telemetry
        subsystem (engine.train calls it once, after the loop, on every
        exit path): the records of the iterations not yet published,
        through ``_publish_grow_records`` — the same publication
        ``finalize_model`` makes when the trees come to the host first."""
        base = min(self._telemetry_iters_base, len(self.models))
        self._publish_grow_records(
            self._fetch_records(self._grow_records[base:]))

    # ------------------------------------------------------------------ model

    def finalize_model(self) -> List[List[Tree]]:
        """Fetch device trees to host Tree objects (one transfer), fold the
        boost-from-average bias into the first tree (gbdt.cpp:445-447).
        The grow records not yet published ride in the same transfer and
        are published here: wherever the trees come to the host, the wave
        loop's counters come with them, and nowhere else."""
        base = min(self._telemetry_iters_base, len(self.models))
        with obs.setup_span("finalize.fetch"):
            if self.pctx.multi_process:
                host = jax.device_get(self.models)
                records = self._fetch_records(self._grow_records[base:])
            else:
                host, records = jax.device_get(
                    (self.models, self._grow_records[base:]))
        self._publish_grow_records(records)
        mappers = self.train_set.mappers
        rfi = self.train_set.real_feature_idx
        forest: List[List[Tree]] = []
        for it_trees in host:
            forest.append([tree_from_device_arrays(t, mappers, rfi) for t in it_trees])
        if forest and abs(self.init_score_value) > 1e-15:
            for k in range(self.num_models):
                forest[0][k].add_bias(self.init_score_value)
        if self.linear_tree and forest:
            # loud degrade accounting: every leaf either fitted a linear
            # model or serialized with an EMPTY feature list (constant
            # fallback) — surface the split so a silently-degraded run is
            # visible in the log and the metrics registry. High-water
            # mark: finalize_model re-runs on every _ensure_finalized, so
            # only iterations not yet accounted count (rollback lowers the
            # mark; retrained iterations count again like new trees).
            base = min(getattr(self, "_linear_counted_iters", 0),
                       len(forest))
            n_lin = n_const = 0
            for it_trees in forest[base:]:
                for t in it_trees:
                    for li in range(t.num_leaves):
                        if t.leaf_features is not None and \
                                len(t.leaf_features[li]):
                            n_lin += 1
                        else:
                            n_const += 1
            self._linear_counted_iters = len(forest)
            if n_lin or n_const:
                reg = obs.get_registry()
                reg.counter("linear.leaves.linear").inc(n_lin)
                reg.counter("linear.leaves.constant").inc(n_const)
                if n_lin == 0 and self.config.tpu_linear_warn_fallback \
                        and not getattr(self, "_linear_warned", False):
                    self._linear_warned = True
                    Log.warning(
                        "linear_tree: every one of the %d leaves degraded "
                        "to constant output (categorical paths, too few "
                        "rows, or ill-conditioned solves) — the model is "
                        "valid but carries no linear leaves; raise "
                        "linear_lambda or check the feature set", n_const)
                else:
                    Log.info("linear_tree: %d linear leaves, %d constant-"
                             "fallback leaves", n_lin, n_const)
        return forest


def create_boosting(config: Config, train_set: ConstructedDataset) -> GBDT:
    """Factory (reference: boosting.cpp:42-66)."""
    btype = config.boosting_normalized
    if btype == "gbdt":
        return GBDT(config, train_set)
    if btype == "goss":
        from .goss import GOSS
        return GOSS(config, train_set)
    if btype == "dart":
        from .dart import DART
        return DART(config, train_set)
    if btype == "rf":
        from .rf import RF
        return RF(config, train_set)
    Log.fatal("Unknown boosting type %s", config.boosting_type)
