"""Device-side tree growth: leaf-wise GBDT trees as one jitted XLA program.

TPU re-architecture of SerialTreeLearner::Train
(reference: src/treelearner/serial_tree_learner.cpp:152-231):

- The reference's per-leaf DataPartition (permuted row indices,
  data_partition.hpp) becomes a flat `leaf_id[num_rows]` vector that drives
  routing/score updates; nothing row-sized is partitioned across waves. A
  wave whose pending leaves hold few enough rows (row_compact) builds the
  rows' order by pending slot with ONE sort, there and then, and its
  histogram pass gathers only those rows (`_rows_by_slot`).
- The reference's one-split-per-iteration loop with histogram pool becomes a
  `lax.while_loop` over *waves*: each wave builds histograms for all pending
  leaves in ONE masked matmul pass (ops/histogram.py), finds their best splits
  (ops/split_finder.py), then applies up to `wave_size` splits chosen by
  global gain order via `top_k` — with wave_size=1 this is exactly the
  reference's leaf-wise ordering; with wave_size=S it amortizes the full-data
  pass over many splits (the TPU analog of the GPU learner batching all
  feature-groups into one kernel launch, gpu_tree_learner.cpp:890-975).
- Sibling histograms come from parent-minus-smaller-child subtraction, as in
  the reference (serial_tree_learner.cpp:354-362, feature_histogram.hpp:64-70),
  via a cached `hist[num_leaves+1, F, B, 3]` tensor in HBM.
- Growth stops when no leaf has a positive-gain split or the leaf budget is
  exhausted (tree_learner guards serial_tree_learner.cpp:172-189).

Everything is fixed-shape; "no split this wave" is a masked no-op, so the
whole tree trains in one XLA dispatch with zero host round-trips (a host
sync drains the asynchronous dispatch queue).

Distributed growth (reference src/treelearner/*parallel*) plugs in through a
``comm`` strategy object (parallel/comm.py): histogram reduction, scalar
psums, and best-split sync happen at exactly the reference's three collective
call sites, but as XLA collectives inside the same while_loop.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .analysis.contracts.registry import trace_entry
from .ops.histogram import (PALLAS_COMPACT_FRAC_CAP, build_histograms,
                            keyed_lookup, one_leaf_form, root_sums,
                            sort_is_one_word)
from .ops.split_finder import SplitCandidates, leaf_output
from .robustness import allowed_host_sync

NEG_INF = -jnp.inf


class TreeArrays(NamedTuple):
    """Array-based tree, LightGBM layout (reference: include/LightGBM/tree.h:356-395).

    Internal node arrays have `num_leaves-1` real rows plus one scratch row for
    masked scatters; leaf arrays likewise `num_leaves`+1. `left_child`/
    `right_child` >= 0 are internal node ids; negative c encodes leaf ~c.
    """
    split_feature: jnp.ndarray    # i32 [M+1] inner feature index
    threshold_bin: jnp.ndarray    # i32 [M+1]
    default_left: jnp.ndarray     # bool [M+1]
    is_cat: jnp.ndarray           # bool [M+1] categorical split
    cat_mask: jnp.ndarray         # bool [M+1, B] left-set over bins (cat)
    left_child: jnp.ndarray       # i32 [M+1]
    right_child: jnp.ndarray      # i32 [M+1]
    split_gain: jnp.ndarray       # f32 [M+1]
    internal_value: jnp.ndarray   # f32 [M+1] would-be output of internal node
    internal_count: jnp.ndarray   # f32 [M+1] (i32 past 2^24 rows: _exact_counts)
    leaf_value: jnp.ndarray       # f32 [L+1]
    leaf_count: jnp.ndarray       # f32 [L+1] (i32 past 2^24 rows: _exact_counts)
    leaf_parent: jnp.ndarray      # i32 [L+1]
    num_leaves: jnp.ndarray       # i32 scalar: leaves actually grown
    # piecewise-linear leaves (linear_tree=true, ops/linear.py): populated
    # by fit_linear_leaves AFTER growth, None otherwise (None is a static
    # empty pytree node, so constant-leaf training never carries them).
    # leaf_feat holds INNER feature indices (-1 pad; all -1 = constant
    # leaf); a linear leaf's output is leaf_const + leaf_coeff . x, with
    # leaf_value kept as the missing-value / degraded fallback.
    leaf_feat: Optional[jnp.ndarray] = None    # i32 [L+1, K]
    leaf_coeff: Optional[jnp.ndarray] = None   # f32 [L+1, K]
    leaf_const: Optional[jnp.ndarray] = None   # f32 [L+1]


class BundleDecode(NamedTuple):
    """Device-side EFB decode tables (efb.py BundlePlan, per scan feature).

    ``X`` passed to the grower holds BUNDLED columns; these map original
    feature f to its bundled column and code range:
    ``orig_bin = code - off[f] if lo[f] <= code < hi[f] else default_bin[f]``.
    ``unpack_bin[f, b]`` is the bundle-bin holding original bin b (-1 for the
    default bin — reconstructed by subtraction, the reference's FixHistogram,
    dataset.cpp:750-769); only the legacy ``tpu_efb_unpack=true`` arm reads
    it. ``code_feat[g, c]`` is the inverse map the NATIVE bundle-space scan
    (ops/split_finder.per_feature_best_bundled) is driven by: the member
    feature owning code c of bundled column g, -1 for unowned positions
    (code 0, bin padding, and the default-bin hole at ``off[f] +
    default_bin[f]`` — its mass is reconstructed by subtraction, never
    stored).
    """
    col: jnp.ndarray          # i32 [F]
    lo: jnp.ndarray           # i32 [F]
    hi: jnp.ndarray           # i32 [F]
    off: jnp.ndarray          # i32 [F]
    unpack_bin: jnp.ndarray   # i32 [F, B]
    code_feat: jnp.ndarray    # i32 [G, Bb]


class RouteTable(NamedTuple):
    """One wave's routing table, keyed by the wave's split ordinal
    (``_apply_wave_splits`` has the columns; ``_route_rows`` applies it)."""
    keys: jnp.ndarray             # i32 [S] leaf split at ordinal k (-1: none)
    rows: jnp.ndarray             # i32 [S, C] the split, one row an ordinal


def _route_bundle(spec: "GrowerSpec", bundle: Optional[BundleDecode]
                  ) -> Optional[BundleDecode]:
    """The bundle tables routing reads: native bundle-space routing only
    (the legacy ``tpu_efb_unpack`` arm decodes per row instead)."""
    return bundle if (bundle is not None and not spec.efb_unpack) else None


def route_table_cols(spec: "GrowerSpec",
                     bundle: Optional[BundleDecode]) -> int:
    """Columns C of the routing table: the split's six, five bundle
    coordinates under native bundle-space routing, the two children's next
    slots under ``row_compact`` (gauge ``route.table_cols``)."""
    return (6 + (5 if _route_bundle(spec, bundle) is not None else 0)
            + (2 if spec.row_compact else 0))


def empty_route_table(spec: "GrowerSpec",
                      bundle: Optional[BundleDecode]) -> RouteTable:
    """The table of a wave that splits nothing: no ordinal carries a key,
    so every row routes to itself (the streamed grower's first wave)."""
    return RouteTable(
        keys=jnp.full(spec.hist_slots, -1, jnp.int32),
        rows=jnp.zeros((spec.hist_slots, route_table_cols(spec, bundle)),
                       jnp.int32))


def _slots_of(pending: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """leaf -> histogram slot (-1: not pending), and the slots' ranks."""
    slot_rank = jnp.cumsum(pending.astype(jnp.int32)) - 1
    return jnp.where(pending, slot_rank, -1).astype(jnp.int32), slot_rank


def decode_bundled_bin(Xb: jnp.ndarray, f: jnp.ndarray,
                       bundle: "BundleDecode",
                       default_bin: jnp.ndarray) -> jnp.ndarray:
    """Per-row original bin of feature ``f[i]`` from the bundled matrix.

    The single source of truth for EFB decode — training-time row routing and
    prediction-time traversal both use it, so they cannot drift apart.
    """
    c = jnp.take_along_axis(Xb, bundle.col[f][:, None],
                            axis=1)[:, 0].astype(jnp.int32)
    in_rng = (c >= bundle.lo[f]) & (c < bundle.hi[f])
    return jnp.where(in_rng, c - bundle.off[f], default_bin[f])


class WaveStats(NamedTuple):
    """What the resident wave loop counts about its own work, exactly, as
    it runs: one small record per tree, written once per wave at index
    ``waves`` and carried in the loop's state (``GrowState.stats``). At
    most ``num_leaves - 1`` waves run (each wave but the last applies a
    split), so the per-wave arrays have that length — a few KB at 255
    leaves, no per-row array, int32 exact up to 2^31 rows per device.

    Under ``shard_map`` each device counts its OWN row shard; the step
    returns the record with a leading device axis and the host takes the
    per-wave maximum over devices, the shard that sets the pace
    (``wave_totals``)."""
    waves: jnp.ndarray          # i32 []  waves run for this tree
    rows_active: jnp.ndarray    # i32 [W] rows of the pending leaves, what
                                # the histogram pass usefully reads; under
                                # a row sample the INCLUDED rows of those
                                # leaves (-1: not counted, row_compact is
                                # off)
    compacted: jnp.ndarray      # bool [W] the pass was compacted: the
                                # lax.cond's own predicate
    rows_split: jnp.ndarray     # i32 [W] rows of the leaves split this
                                # wave: what routing and the partition
                                # usefully move
    scan_pending: jnp.ndarray   # i32 [W] of the slots the wave's tail
                                # covered, those that held a leaf (a
                                # pending leaf or its sibling by
                                # subtraction)
    scan_slots: Optional[jnp.ndarray] = None  # i32 [W] slots the cache's
                                # write-back and the split scan covered:
                                # 2 x b x the blocks the loop ran. None
                                # where the tail keeps its static form
                                # (scan_block_pairs: a narrow table), 2 x
                                # hist_slots every wave and nothing to
                                # count: that program carries no counter
    one_leaf: Optional[jnp.ndarray] = None  # bool [W] the wave's pending
                                # leaves numbered ONE (the root's pass, its
                                # smaller child's) and its histograms were
                                # built in the one-leaf form of the chunk
                                # matmul: the lax.switch's own predicate.
                                # None where the table has no such form
                                # (ops/histogram.one_leaf_form): that
                                # program carries no counter


def _empty_stats(L: int, blocked_tail: bool, one_leaf: bool) -> WaveStats:
    W = max(L - 1, 1)
    return WaveStats(waves=jnp.asarray(0, jnp.int32),
                     rows_active=jnp.zeros(W, jnp.int32),
                     compacted=jnp.zeros(W, bool),
                     rows_split=jnp.zeros(W, jnp.int32),
                     scan_pending=jnp.zeros(W, jnp.int32),
                     scan_slots=(jnp.zeros(W, jnp.int32) if blocked_tail
                                 else None),
                     one_leaf=jnp.zeros(W, bool) if one_leaf else None)


def wave_totals(stats, rows_per_device: int, chunk_rows: int,
                hist_slots: int) -> Dict[str, int]:
    """Per-tree totals the host derives from one tree's ``WaveStats`` as
    fetched (numpy, leading device axis ``[D, ...]``), with no model of the
    loop: every number is a sum over the waves the loop itself recorded.
    With several devices each per-wave term is the maximum over devices.

    ``hist_rows_touched`` is what the histogram kernel passed over: all of
    a device's rows for a streamed pass, ``ceil(rows_active / chunk) *
    chunk`` for a compacted one (build_histograms' trip count).
    ``hist_rows_active`` is the useful part of it (None where the loop did
    not count it). Routing and the partition pass over every row every
    wave (``rows_routed``); ``rows_split`` is the useful part of that.
    ``hist_chunks`` is the chunks the passes ran (each folds into the
    accumulator once). ``scan_slots`` is the slots the waves' tails covered
    (the loop's own count: the blocks it ran, or 2 x ``hist_slots`` a wave
    in the static form); ``scan_slots_pending`` of them held a leaf.
    ``one_leaf_passes`` is the waves that built their histograms in the
    one-leaf form, ``hist_rows_one_leaf`` / ``hist_chunks_one_leaf`` the
    touched rows and the chunks of those waves (None where the program has
    no such form and carries no counter)."""
    waves = int(np.max(stats.waves))

    def per_wave(a, dtype):                              # -> [D, waves]
        return np.asarray(a, dtype).reshape(-1, np.shape(a)[-1])[:, :waves]

    active = per_wave(stats.rows_active, np.int64)
    compacted = per_wave(stats.compacted, bool)
    split = per_wave(stats.rows_split, np.int64)
    scanned = per_wave(stats.scan_pending, np.int64)
    # the static tail (no counter) covers every slot pair every wave
    covered = (per_wave(stats.scan_slots, np.int64)
               if stats.scan_slots is not None
               else np.full_like(scanned, 2 * hist_slots))
    chunks = np.minimum(-(-np.maximum(active, 0) // chunk_rows),
                        rows_per_device // chunk_rows)
    touched = np.where(compacted, chunks * chunk_rows, rows_per_device)
    # a wave counts as streamed when any shard streams it: that shard sets
    # the wave's pace
    streamed = int((~compacted).any(axis=0).sum())
    if stats.one_leaf is None:
        one_leaf = one_leaf_rows = None
    else:
        # replicated across shards: the pending leaves are the tree's
        one_leaf = per_wave(stats.one_leaf, bool).any(axis=0)
        one_leaf_rows = int(touched.max(axis=0)[one_leaf].sum())
    return {"waves": waves,
            "one_leaf_passes": None if one_leaf is None else int(one_leaf.sum()),
            "hist_rows_one_leaf": one_leaf_rows,
            "hist_chunks_one_leaf": (None if one_leaf is None
                                     else one_leaf_rows // chunk_rows),
            # (streamed, compacted) passes of each shard, where there are
            # several: shards that took different arms in a wave are seen
            "shard_passes": ([(int(w), waves - int(w))
                              for w in (~compacted).sum(axis=1)]
                             if compacted.shape[0] > 1 else []),
            "stream_passes": streamed,
            "compact_passes": waves - streamed,
            "hist_rows_touched": int(touched.max(axis=0).sum()),
            "hist_chunks": int(touched.max(axis=0).sum()) // chunk_rows,
            "scan_slots": int(covered.max(axis=0).sum()),
            "scan_slots_pending": int(scanned.max(axis=0).sum()),
            "hist_rows_active": (int(active.max(axis=0).sum())
                                 if (active >= 0).all() else None),
            "rows_routed": waves * int(rows_per_device),
            "rows_split": int(split.max(axis=0).sum())}


class GrowState(NamedTuple):
    """Wave-loop carry. Buffer lifetime note: everything here — including
    the [L+1, F, B, 3] histogram cache, the largest allocation after the
    code matrix — is `lax.while_loop` carry, which XLA aliases in place
    across waves; the cross-ITERATION carries (scores, bagging mask) are
    donated at the jit boundary instead (boosting/gbdt.py `donate_argnums`),
    so neither layer pays an allocate+copy per update."""
    tree: TreeArrays
    leaf_id: jnp.ndarray          # i32 [N]
    hist: jnp.ndarray             # f32 [L+1, F, B, 3] per-leaf histogram cache
    sum_g: jnp.ndarray            # f32 [L+1]
    sum_h: jnp.ndarray            # f32 [L+1]
    cnt: jnp.ndarray              # f32 [L+1]
    leaf_depth: jnp.ndarray       # i32 [L+1]
    leaf_is_right: jnp.ndarray    # bool [L+1]
    cand: SplitCandidates         # per-leaf best-split cache, arrays [L+1]
    needs_hist: jnp.ndarray       # bool [L+1]
    sib_leaf: jnp.ndarray         # i32 [L+1] sibling to derive by subtraction
    parent_cache: jnp.ndarray     # i32 [L+1] cache row holding the parent hist
    num_leaves_cur: jnp.ndarray   # i32
    done: jnp.ndarray             # bool
    # The only per-row carry besides leaf_id: the histogram slot of the
    # row's leaf in the NEXT wave (-1: its leaf is not pending), written by
    # the routing pass that moved the row (step 8), so no wave pays a second
    # per-row table_lookup. None with row_compact off (a static empty pytree
    # leaf, so the while_loop carry stays structurally consistent).
    slot_row: Optional[jnp.ndarray] = None   # i32 [N]
    # the loop's own counters (resident loop only; None under streaming,
    # where the host drives the waves)
    stats: Optional[WaveStats] = None


@dataclass(frozen=True)
class GrowerSpec:
    """Static (trace-time) configuration of the grower."""
    num_leaves: int
    num_features: int             # width of X (histogram-build features)
    num_bins_padded: int
    chunk_rows: int               # rows a chunk of the histogram pass
                                  # (ops/histogram.hist_pass_shape)
    hist_slots: int               # leaves histogrammed per pass == max splits/wave
    wave_size: int                # splits applied per wave (1 = exact leaf-wise)
    max_depth: int                # <=0: unlimited
    lambda_l1: float
    lambda_l2: float
    min_data_in_leaf: float
    min_sum_hessian_in_leaf: float
    min_gain_to_split: float
    row_compact: bool = True      # a wave may COMPACT its histogram pass:
                                  # read only the pending leaves' rows,
                                  # through a slot-grouped index built by
                                  # one sort in that arm (phase
                                  # wave.partition); a streamed wave builds
                                  # nothing. False: every wave streams
    compact_frac: float = 1.0     # a wave compacts its histogram pass when
                                  # n_active < int(N * compact_frac), else
                                  # it streams all N rows. RESOLVED where
                                  # the spec is built (tpu_compact_frac, 0 =
                                  # auto = ops/histogram.compact_break_even
                                  # of the shapes, capped at 0.25 for the
                                  # Pallas kernels), so the predicate is a
                                  # static int compare. 1.0 = stream a full
                                  # root only: every later wave histograms
                                  # smaller children, under half of the rows
    one_leaf_frac: float = 0.0    # > 0: a wave whose pending leaves number
                                  # ONE builds its histogram in the one-leaf
                                  # form of the chunk matmul
                                  # (ops/histogram.one_leaf_form of the
                                  # build's shapes), compacted when n_active
                                  # < int(N * one_leaf_frac): that form's
                                  # own break-even, resolved where the spec
                                  # is built (one_leaf_break_even). 0: the
                                  # table, the weight mode, the kernel or
                                  # the device has no such form and every
                                  # wave takes the general one
    hist_bins: int = 0            # bin axis of the histogram BUILD (EFB bundle
                                  # space); 0 = num_bins_padded (unbundled)
    efb_unpack: bool = False      # LEGACY EFB scan arm (tpu_efb_unpack):
                                  # unpack bundle-space histograms to
                                  # [T, F, B, 3] before the split scan and
                                  # route rows through the per-row
                                  # decode_bundled_bin gather. False (the
                                  # default) scans and routes in bundle
                                  # space natively — the A/B + parity pin
                                  # is tests/test_efb_bundlespace.py
    code_mode: Optional[str] = None  # packed-row code layout (histogram.py
                                  # code_mode_for): u8 | u16 | u4 | u6;
                                  # None = plain byte layout by X dtype
    hist_kernel: str = "xla"      # "xla" (one-hot matmul) | "pallas" (fused
                                  # VMEM-accumulator kernel, ops/pallas_histogram.py)
    hist_f64: bool = False        # the weight mode, one of two. False: bf16
                                  # hi/lo channel pairs (~f32 sums). True:
                                  # f32 channels at Precision.HIGHEST with a
                                  # Kahan-compensated chunk accumulation,
                                  # ~f64-accurate bin sums like the
                                  # reference's double HistogramBinEntry
                                  # (bin.h:29-31); xla kernel only
    # categorical split search (reference config.h:230-234)
    use_categorical: bool = False
    cat_features: tuple = ()      # STATIC inner indices of categorical
                                  # features — the native EFB arm's cat
                                  # scan unpacks ONLY these members'
                                  # bundle columns (a [T, Fc, B, 3]
                                  # gather instead of re-paying the full
                                  # [T, F, B, 3] decode the redesign
                                  # deleted); empty when none
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: float = 100.0

    def hyperparams(self) -> Dict[str, float]:
        return dict(lambda_l1=self.lambda_l1, lambda_l2=self.lambda_l2,
                    min_data_in_leaf=self.min_data_in_leaf,
                    min_sum_hessian_in_leaf=self.min_sum_hessian_in_leaf,
                    min_gain_to_split=self.min_gain_to_split)

    def cat_hyperparams(self) -> Dict[str, float]:
        return dict(cat_smooth=self.cat_smooth, cat_l2=self.cat_l2,
                    max_cat_threshold=self.max_cat_threshold,
                    max_cat_to_onehot=self.max_cat_to_onehot,
                    min_data_per_group=self.min_data_per_group)


def _empty_tree(L: int, B: int) -> TreeArrays:
    M = L - 1
    return TreeArrays(
        split_feature=jnp.zeros(M + 1, jnp.int32),
        threshold_bin=jnp.zeros(M + 1, jnp.int32),
        default_left=jnp.zeros(M + 1, bool),
        is_cat=jnp.zeros(M + 1, bool),
        cat_mask=jnp.zeros((M + 1, B), bool),
        left_child=jnp.full(M + 1, -1, jnp.int32),
        right_child=jnp.full(M + 1, -1, jnp.int32),
        split_gain=jnp.zeros(M + 1, jnp.float32),
        internal_value=jnp.zeros(M + 1, jnp.float32),
        internal_count=jnp.zeros(M + 1, jnp.float32),
        leaf_value=jnp.zeros(L + 1, jnp.float32),
        leaf_count=jnp.zeros(L + 1, jnp.float32),
        leaf_parent=jnp.full(L + 1, -1, jnp.int32),
        num_leaves=jnp.asarray(1, jnp.int32),
    )


def _unpack_bundled(hist_g: jnp.ndarray, bundle: BundleDecode,
                    pg: jnp.ndarray, ph: jnp.ndarray, pc: jnp.ndarray,
                    default_bin: jnp.ndarray) -> jnp.ndarray:
    """EFB unpack: [T, G, Bb, 3] bundle-space histograms -> [T, F, B, 3]
    original-feature space, reconstructing each feature's default bin by
    subtraction from the leaf totals (reference Dataset::FixHistogram,
    dataset.cpp:750-769 — applied per scanned feature there too).

    LEGACY arm only (``tpu_efb_unpack=true``): the default path scans the
    bundle-space histogram natively (ops/split_finder.py
    per_feature_best_bundled) and never materializes this [T, F, B] decode
    — the gather here dominated the round-5 sparse wave and was the whole
    3.5x EFB-on-TPU loss."""
    from .ops.split_finder import unpack_bundled_hist
    return unpack_bundled_hist(hist_g, bundle.col, bundle.unpack_bin,
                               pg, ph, pc, default_bin)


def _empty_cand(L: int, B: int) -> SplitCandidates:
    return SplitCandidates(
        gain=jnp.full(L + 1, NEG_INF, jnp.float32),
        feature=jnp.zeros(L + 1, jnp.int32),
        threshold=jnp.zeros(L + 1, jnp.int32),
        default_left=jnp.zeros(L + 1, bool),
        left_g=jnp.zeros(L + 1, jnp.float32),
        left_h=jnp.zeros(L + 1, jnp.float32),
        left_c=jnp.zeros(L + 1, jnp.float32),
        is_cat=jnp.zeros(L + 1, bool),
        cat_mask=jnp.zeros((L + 1, B), bool),
    )


# ---- the wave's tail, over the slots the wave holds -------------------------
# The pending leaves take slots 0 .. k-1 (_slots_of: the rank of a cumsum) and
# k is 1, 1, 2, 4, 8, 16 and then S for a 255-leaf tree, while the cache's
# subtract-and-write-back and the split scan are independent slot by slot. On
# a WIDE table their arrays are large ([2S, F, B, 3] f32: 307 MB at 2,000
# columns) and all S slot pairs at once cost 15-18 + 6.4 ms a wave on the v5e
# whatever the wave holds, so they run over blocks of b slot pairs under one
# loop of ceil(k / b) trips: 1.37 + 0.19 ms a block of 4 pairs there, a full
# wave 9.4 + 1.3 ms and the first four waves 1.6 ms each (whole trees at
# 400,000 x 2,000, seconds a tree: b = 1 3.544, 2 1.387, 3 1.391, 4 1.363,
# 5 1.409, 7 1.425, 9 1.444, 13 1.502, all 25 at once 1.637; my chip run,
# PR 35: a pair costs 0.39 ms in a block of 4 and 1.08 in one of 25). On a
# narrow table the same arrays are a few MB, the tail's ~100 fusions are
# launch-bound and every block would cost what the whole does: there b = S and
# the tail keeps its static form, with no loop. b follows from the shapes: as
# many slot pairs as bring one block's [2b, F, B, 3] f32 to
# _SCAN_BLOCK_BYTES, in blocks of equal size.
_SCAN_BLOCK_BYTES = 40 << 20


def scan_block_pairs(hist_slots: int, features: int, bins: int) -> int:
    """Slot pairs (a pending leaf and its sibling) a block of the wave's tail
    covers, from the width of the histograms it scans (``features`` x
    ``bins`` AFTER the reduce: a device's block): a static function of the
    shapes. ``hist_slots`` = the static form, no loop (gauge
    ``scan.block_slots``)."""
    pair_bytes = 2 * int(features) * int(bins) * 3 * 4
    want = -(-_SCAN_BLOCK_BYTES // pair_bytes)
    if want >= hist_slots:
        return int(hist_slots)
    blocks = -(-hist_slots // want)
    return -(-hist_slots // blocks)


def _unbundles_early(spec: "GrowerSpec", comm, bundled: bool) -> bool:
    """The LEGACY EFB arm (``tpu_efb_unpack``) under a row-sharded strategy
    unpacks bundle-space histograms to feature space BEFORE the collective,
    with the shard's own leaf totals; serial and bundled-block layouts
    unpack at scan time."""
    return (bundled and spec.efb_unpack
            and getattr(comm, "axis", None) is not None
            and not getattr(comm, "bundled_blocks", False))


def scan_hist_shape(spec: "GrowerSpec", comm, hist_cols: int,
                    bundled: bool) -> Tuple[int, int]:
    """(features, bins) of the histograms a device caches and scans: what
    ``comm.reduce_hist`` leaves of the ``hist_cols`` columns it histograms.
    Bundle space under EFB, except where the legacy arm unpacks before the
    collective."""
    if _unbundles_early(spec, comm, bundled):
        return (comm.reduced_hist_features(spec.num_features),
                spec.num_bins_padded)
    return (comm.reduced_hist_features(hist_cols),
            spec.hist_bins or spec.num_bins_padded)


def _scan_slot_pairs(state: "GrowState", hist, new_hist, leaves, bm, spec,
                     comm, scan_bundle, default_bin, row_by_row: bool):
    """Steps 3 and 4 for the slots that serve ``leaves`` [n] (L: none) and
    built ``new_hist`` [n, F, B, 3]: the siblings by subtraction from the
    parents' rows of the cache ``hist``, both written back, and this
    device's scan of the 2n histograms (``comm.scan_block``: no collective).
    Returns (cache, scanned leaves [2n], their histograms, their leaf sums,
    the scan).

    ``row_by_row`` reads the parents' rows as n slices of the cache and not
    as one gather: the v5e's gather first copies the WHOLE cache, in column
    pieces, whatever it fetches (4.7 ms of the 1.57 GB cache at 2,000
    columns for 25 rows or for 5: my chip run, PR 35), which a block of a
    few slots cannot afford and a narrow table's small cache does not
    notice."""
    L = spec.num_leaves
    # "wave.cache": the parent read, the subtraction and the two write-backs
    # of the [L+1, F, B, 3] cache, [n, F, B, 3] each (154 MB for 25 slots at
    # 2,000 columns), named apart from the scan they feed
    slot_valid = leaves < L
    sibs = state.sib_leaf[leaves]                             # [n]
    with jax.named_scope("wave.cache"):
        parent_rows = state.parent_cache[leaves]              # [n]
        if row_by_row:
            parent_hist = jnp.stack([
                jax.lax.dynamic_index_in_dim(hist, parent_rows[j],
                                             keepdims=False)
                for j in range(parent_rows.shape[0])])
        else:
            parent_hist = hist[parent_rows]                   # [n, F, B, 3]
        sib_hist = parent_hist - new_hist
        hist = hist.at[jnp.where(slot_valid, leaves, L)].set(new_hist)
        hist = hist.at[jnp.where(slot_valid, sibs, L)].set(sib_hist)

    scan_leaves = jnp.concatenate([leaves, jnp.where(slot_valid, sibs, L)])
    scan_hist = jnp.concatenate([new_hist, sib_hist], axis=0)  # [2n, F, B, 3]
    find_bundle = None
    if scan_bundle is not None:
        if spec.efb_unpack:
            # legacy arm: materialize the [2n, F, B, 3] feature-space
            # decode (the gather the native path exists to delete)
            scan_hist = _unpack_bundled(
                scan_hist, scan_bundle, state.sum_g[scan_leaves],
                state.sum_h[scan_leaves], state.cnt[scan_leaves], default_bin)
        else:
            find_bundle = scan_bundle
    sums = (state.sum_g[scan_leaves], state.sum_h[scan_leaves],
            state.cnt[scan_leaves])
    local = comm.scan_block(scan_hist, *sums, bm, spec, bundle=find_bundle)
    return hist, scan_leaves, scan_hist, sums, local


def _scan_held_slots(state: "GrowState", new_hist, leaf_of_slot, b: int, bm,
                     spec, comm, scan_bundle, default_bin):
    """``_scan_slot_pairs`` over the occupied prefix of the wave's slots, b
    slot pairs a trip: one copy of the scan in the executable, the cache
    carried through the loop and written in place. Slots past the last
    block are not read, subtracted, written or scanned: they serve no leaf,
    and their rows of the scan stay inert (gain -inf, zeros). Returns
    (cache, scanned leaves [2S], the scan [2S, ...], slots covered)."""
    L, S = spec.num_leaves, spec.hist_slots
    held = jnp.sum((leaf_of_slot < L).astype(jnp.int32))
    trips = (held + (b - 1)) // b

    def block(i, hist):
        # the last block of an S that b does not divide starts early
        # (dynamic_slice clamps): the slots the block before it covered
        # are served as slots of no leaf
        start = jnp.minimum(i * b, S - b)
        slot = start + jnp.arange(b, dtype=jnp.int32)
        fresh = slot >= i * b
        leaves = jnp.where(
            fresh, jax.lax.dynamic_slice_in_dim(leaf_of_slot, start, b), L)
        hist, _, _, _, local = _scan_slot_pairs(
            state, hist, jax.lax.dynamic_slice_in_dim(new_hist, start, b),
            leaves, bm, spec, comm, scan_bundle, default_bin, row_by_row=True)
        # the block's rows of the [2S] scan: the leaves, then their siblings
        rows = jnp.where(jnp.concatenate([fresh, fresh]),
                         jnp.concatenate([slot, slot + S]), 2 * S)
        return hist, local, rows

    local0 = jax.tree.map(
        lambda a: jnp.zeros((2 * S,) + a.shape[1:], a.dtype),
        jax.eval_shape(lambda: block(jnp.int32(0), state.hist)[1]))
    local0 = local0._replace(gain=jnp.full_like(local0.gain, NEG_INF))

    def next_block(carry):
        i, hist, local = carry
        hist, found, rows = block(i, hist)
        local = jax.tree.map(
            lambda all_, new: all_.at[rows].set(new, mode="drop"), local, found)
        return i + 1, hist, local

    _, hist, local = jax.lax.while_loop(
        lambda carry: carry[0] < trips, next_block,
        (jnp.int32(0), state.hist, local0))
    scan_leaves = jnp.concatenate([leaf_of_slot, jnp.where(
        leaf_of_slot < L, state.sib_leaf[leaf_of_slot], L)])
    return hist, scan_leaves, local, 2 * b * trips


@jax.named_scope("wave.split")
def _apply_wave_splits(state: GrowState, new_hist: jnp.ndarray,
                       leaf_of_slot: jnp.ndarray, bm, spec: "GrowerSpec",
                       comm, scan_bundle: Optional[BundleDecode],
                       num_bins: jnp.ndarray, missing_code: jnp.ndarray,
                       default_bin: jnp.ndarray,
                       route_bundle: Optional[BundleDecode] = None):
    """Steps 3-6 of one wave — cache write + sibling subtraction, split
    scan, split choice, tree/leaf-state apply — plus the wave's
    ``RouteTable`` (one row a split ordinal) and categorical left-set mask
    the per-row routing pass consumes.

    Shared VERBATIM by the resident wave body (``grow_tree``) and the
    streamed ``wave_update`` (``StreamedGrower``): residency is a transport
    decision, so the split math must have exactly one home or the two
    modes drift apart bit by bit (and its device operations carry the
    scope ``wave.split`` in both; ``_route_rows``: ``wave.route``).
    ``new_hist`` arrives post-``reduce_hist``
    (and post-early-unbundle where that applies); ``scan_bundle`` is the
    EFB decode table when the histograms are bundle-space — with
    ``spec.efb_unpack`` the LEGACY arm unpacks them to feature space here
    (serial / bundled-block layouts), otherwise the scan runs natively on
    bundle space (comm.scan_block -> per_feature_best_bundled) and only
    the winning (bundled column, bundle bin) is translated back to
    (original feature, original bin) — the reference's FeatureGroup
    discipline. ``route_bundle`` (native arm only, GLOBAL tables) extends
    the routing table with the split feature's bundle column/range so the
    routing pass compares bundled codes directly instead of gathering a
    per-row decode.

    Returns ``(state', table, map_mask, n_apply, scan_slots)`` with
    ``state'`` carrying every field EXCEPT the per-row ones (leaf_id and the
    next wave's slot of each row), which the caller owns and the routing
    pass writes; ``scan_slots`` is the slots steps 3-4 covered this wave
    (None in the static form: all 2S, whatever the wave held).
    """
    L = spec.num_leaves
    M = L - 1
    S = spec.hist_slots
    B = spec.num_bins_padded
    leaf_iota = jnp.arange(L + 1, dtype=jnp.int32)

    # ---- 3-4. cache write + sibling by subtraction, split scan ----------
    # over the slots the wave holds, in blocks sized by the table's width
    # (scan_block_pairs); a narrow table takes all S slot pairs at once
    b = scan_block_pairs(S, *new_hist.shape[1:3])
    if b >= S:
        hist, scan_leaves, scan_hist, sums, local = _scan_slot_pairs(
            state, state.hist, new_hist, leaf_of_slot, bm, spec, comm,
            scan_bundle, default_bin, row_by_row=False)
        pick_cols = lambda cols: jnp.take_along_axis(       # noqa: E731
            scan_hist, cols[:, :, None, None], axis=1)
        scan_slots = None
    else:
        hist, scan_leaves, local, scan_slots = _scan_held_slots(
            state, new_hist, leaf_of_slot, b, bm, spec, comm, scan_bundle,
            default_bin)
        sums = tuple(a[scan_leaves]
                     for a in (state.sum_g, state.sum_h, state.cnt))
        # a slot's histograms are its leaves' rows of the cache by now
        pick_cols = lambda cols: hist[scan_leaves[:, None], cols]  # noqa: E731
    # candidate features are GLOBAL indices; under feature/data
    # parallelism this ends in an all-gather argmax across devices
    # (reference SyncUpGlobalBestSplit, parallel_tree_learner.h:184-207)
    cand_new = comm.sync_splits(
        local, pick_cols, *sums, bm, spec,
        bundle=None if spec.efb_unpack else scan_bundle)
    cand = SplitCandidates(*[
        old.at[scan_leaves].set(new) for old, new in zip(state.cand, cand_new)])
    cand = cand._replace(gain=cand.gain.at[L].set(NEG_INF))  # keep scratch row inert
    needs_hist = jnp.zeros_like(state.needs_hist)

    # ---- 5. choose splits to apply this wave ---------------------------
    active = leaf_iota < state.num_leaves_cur
    depth_ok = (spec.max_depth <= 0) | (state.leaf_depth < spec.max_depth)
    gains = jnp.where(active & depth_ok & jnp.isfinite(cand.gain), cand.gain, NEG_INF)
    top_gain, top_leaf = jax.lax.top_k(gains, S)
    budget = L - state.num_leaves_cur
    cap = min(spec.wave_size, S) if spec.wave_size > 0 else S
    srank = jnp.arange(S, dtype=jnp.int32)
    apply = jnp.isfinite(top_gain) & (srank < budget) & (srank < cap)
    n_apply = jnp.sum(apply.astype(jnp.int32))

    # ---- 6. apply: tree arrays + leaf state ----------------------------
    p = jnp.where(apply, top_leaf, L)                         # split leaf (L=dummy)
    nid = jnp.where(apply, state.num_leaves_cur - 1 + srank, M)  # new internal node
    q = jnp.where(apply, state.num_leaves_cur + srank, L)     # new right leaf

    lg = cand.left_g[p]
    lh = cand.left_h[p]
    lc = cand.left_c[p]
    pg, ph, pc = state.sum_g[p], state.sum_h[p], state.cnt[p]
    rg_, rh_, rc_ = pg - lg, ph - lh, pc - lc

    t = state.tree
    t = t._replace(
        split_feature=t.split_feature.at[nid].set(cand.feature[p]),
        threshold_bin=t.threshold_bin.at[nid].set(cand.threshold[p]),
        default_left=t.default_left.at[nid].set(cand.default_left[p]),
        is_cat=t.is_cat.at[nid].set(cand.is_cat[p]),
        cat_mask=t.cat_mask.at[nid].set(cand.cat_mask[p]),
        split_gain=t.split_gain.at[nid].set(cand.gain[p]),
        internal_value=t.internal_value.at[nid].set(
            leaf_output(pg, ph, spec.lambda_l1, spec.lambda_l2)),
        internal_count=t.internal_count.at[nid].set(pc),
        left_child=t.left_child.at[nid].set(-p - 1),
        right_child=t.right_child.at[nid].set(-q - 1),
    )
    # re-wire the parent pointer that used to reach leaf p
    prev_node = t.leaf_parent[p]
    wire_left = jnp.where(apply & (prev_node >= 0) & ~state.leaf_is_right[p],
                          prev_node, M)
    wire_right = jnp.where(apply & (prev_node >= 0) & state.leaf_is_right[p],
                           prev_node, M)
    t = t._replace(
        left_child=t.left_child.at[wire_left].set(jnp.where(apply, nid, t.left_child[wire_left])),
        right_child=t.right_child.at[wire_right].set(jnp.where(apply, nid, t.right_child[wire_right])),
        leaf_parent=t.leaf_parent.at[p].set(nid).at[q].set(nid),
        leaf_value=t.leaf_value
            .at[p].set(leaf_output(lg, lh, spec.lambda_l1, spec.lambda_l2))
            .at[q].set(leaf_output(rg_, rh_, spec.lambda_l1, spec.lambda_l2)),
        leaf_count=t.leaf_count.at[p].set(lc).at[q].set(rc_),
        num_leaves=state.num_leaves_cur + n_apply,
    )
    leaf_is_right = state.leaf_is_right.at[p].set(False).at[q].set(True)

    sum_g = state.sum_g.at[p].set(lg).at[q].set(rg_)
    sum_h = state.sum_h.at[p].set(lh).at[q].set(rh_)
    cnt = state.cnt.at[p].set(lc).at[q].set(rc_)
    new_depth = state.leaf_depth[p] + 1
    leaf_depth = state.leaf_depth.at[p].set(new_depth).at[q].set(new_depth)
    cand = cand._replace(gain=cand.gain.at[p].set(NEG_INF).at[q].set(NEG_INF))

    # next wave: histogram the smaller child, derive the larger (ref
    # serial_tree_learner.cpp:354-362)
    left_smaller = lc <= rc_
    smaller = jnp.where(left_smaller, p, q)
    larger = jnp.where(left_smaller, q, p)
    needs_hist = needs_hist.at[smaller].set(apply, mode="drop")
    needs_hist = needs_hist.at[L].set(False)
    sib_leaf = state.sib_leaf.at[smaller].set(larger)
    parent_cache = state.parent_cache.at[smaller].set(jnp.where(apply, p, L))

    # ---- routing table (applied per row by _route_rows) ----------------
    # Keyed by the wave's split ORDINAL, not by leaf id: row k holds the
    # k-th chosen split and keys[k] the leaf it splits (-1, a key no row
    # carries, where ordinal k applies nothing), so the routing pass
    # resolves a row by ONE [N, S] match against the keys (keyed_lookup)
    # where a table over all L + 1 leaves cost a 256-wide one-hot a row for
    # a wave that splits at most S = 25 of them: 16.5 ms a wave at
    # 14,680,064 rows against 0.88 (my chip run; PERF.md, PR 33). A row whose
    # leaf is not split matches nothing and reads zeros, so the two columns
    # whose "nothing" is -1 are stored offset by one. Columns:
    #   0: split feature + 1 (0 = leaf not split this wave)
    #   1: threshold bin
    #   2: missing bin code + 1 (0 = feature has no missing bin) folded
    #      from (missing_code, num_bins, default_bin) at split time — the
    #      reference's NumericalDecision missing handling (tree.h:218)
    #   3: right-child leaf   4: default_left   5: is_cat
    # Native bundle-space routing (route_bundle set) appends the winning
    # feature's bundle coordinates — resolved here for the <= wave_size
    # chosen splits only, never per row (the reference translates a
    # FeatureGroup threshold back the same way):
    #   6: bundled column   7: lo   8: hi   9: off   10: default bin
    # Under row_compact the last two columns carry the NEXT wave's
    # histogram slot + 1 of the left and of the right child (0 = not
    # pending: needs_hist is final above), so the slot of every moved row
    # rides on the same pass.
    sf = cand.feature[p]
    sf_safe = jnp.maximum(sf, 0)
    mc_s, nb_s, db_s = (missing_code[sf_safe], num_bins[sf_safe],
                        default_bin[sf_safe])
    miss_bin = jnp.where(mc_s == 2, nb_s - 1,
                         jnp.where(mc_s == 1, db_s, -1))
    cols = [sf.astype(jnp.int32) + 1, cand.threshold[p],
            miss_bin.astype(jnp.int32) + 1, q.astype(jnp.int32),
            cand.default_left[p].astype(jnp.int32),
            cand.is_cat[p].astype(jnp.int32)]
    if route_bundle is not None:
        cols += [route_bundle.col[sf_safe], route_bundle.lo[sf_safe],
                 route_bundle.hi[sf_safe], route_bundle.off[sf_safe],
                 db_s.astype(jnp.int32)]
    if spec.row_compact:
        slot_next = _slots_of(needs_hist)[0]
        cols += [slot_next[p] + 1, slot_next[q] + 1]
    table = RouteTable(keys=jnp.where(apply, p, -1),
                       rows=jnp.stack(cols, axis=-1))
    map_mask = None
    if spec.use_categorical:
        map_mask = jnp.zeros((L + 1, B), bool).at[p].set(cand.cat_mask[p],
                                                         mode="drop")

    done = (n_apply == 0) | (state.num_leaves_cur + n_apply >= L)
    state2 = state._replace(
        tree=t, hist=hist, sum_g=sum_g, sum_h=sum_h, cnt=cnt,
        leaf_depth=leaf_depth, leaf_is_right=leaf_is_right, cand=cand,
        needs_hist=needs_hist, sib_leaf=sib_leaf, parent_cache=parent_cache,
        num_leaves_cur=state.num_leaves_cur + n_apply, done=done)
    return state2, table, map_mask, n_apply, scan_slots


@trace_entry("routing.bundle_space")
@jax.named_scope("wave.route")
def _route_rows(X: jnp.ndarray, lid: jnp.ndarray, table: RouteTable,
                map_mask: Optional[jnp.ndarray], spec: "GrowerSpec",
                bundle: Optional[BundleDecode], default_bin: jnp.ndarray):
    """Step 7: apply one wave's routing table to the rows of ``X``.

    The only wave computation that touches the code matrix besides the
    histogram build — under streaming it runs per shard (fused ahead of the
    shard's histogram leg) on exactly these ops. Returns
    ``(leaf_id, f_row, slot_row)``: every row's leaf after the wave, its
    split feature (-1: its leaf was not split) and, under
    ``spec.row_compact``, the histogram slot of its leaf in the next wave
    (-1: not pending; else None)."""
    packed = keyed_lookup(lid, table.keys, table.rows)        # [N, C]
    f_row = packed[:, 0] - 1
    thr_row = packed[:, 1]
    miss_row = packed[:, 2] - 1
    right_row = packed[:, 3]
    dl_row = packed[:, 4] != 0
    f_safe = jnp.maximum(f_row, 0)
    if bundle is None:
        # split-feature bin via one-hot multiply-sum over the F lanes —
        # a fused VPU stream, vs take_along_axis's per-row gather
        f_onehot = f_safe[:, None] == jnp.arange(X.shape[1],
                                                 dtype=jnp.int32)[None, :]
        x_bin = jnp.sum(X.astype(jnp.int32) * f_onehot, axis=1)
    elif not spec.efb_unpack:
        # native bundle-space routing: the table carries the split's
        # bundle coordinates, so the row decision is the bundled code
        # against the bundle-space range/threshold directly (the
        # reference's DenseBin::Split min_bin/max_bin compare) — same
        # one-hot multiply-sum idiom as the unbundled path, over G << F
        # columns, and ZERO per-row table gathers (the
        # decode_bundled_bin take_along_axis this path deletes was the
        # routing half of the round-5 3.5x EFB loss)
        col_row = packed[:, 6]
        lo_row = packed[:, 7]
        hi_row = packed[:, 8]
        off_row = packed[:, 9]
        db_row = packed[:, 10]
        g_onehot = col_row[:, None] == jnp.arange(X.shape[1],
                                                  dtype=jnp.int32)[None, :]
        c = jnp.sum(X.astype(jnp.int32) * g_onehot, axis=1)
        in_rng = (c >= lo_row) & (c < hi_row)
        x_bin = jnp.where(in_rng, c - off_row, db_row)
    else:
        # legacy arm (tpu_efb_unpack=true): per-row decode gather
        x_bin = decode_bundled_bin(X, f_safe, bundle, default_bin)
    go_left = jnp.where(x_bin == miss_row, dl_row, x_bin <= thr_row)
    if spec.use_categorical:
        # categorical routing: bin in the split's left-set -> left
        # (reference Tree::CategoricalDecision, tree.h:257-284)
        cat_row = packed[:, 5] != 0
        go_left_cat = jnp.take_along_axis(map_mask[lid], x_bin[:, None],
                                          axis=1)[:, 0]
        go_left = jnp.where(cat_row, go_left_cat, go_left)
    leaf_id = jnp.where((f_row >= 0), jnp.where(go_left, lid, right_row), lid)
    slot_row = None
    if spec.row_compact:
        # a row of an unsplit leaf read zeros: -1, not pending
        slot_row = jnp.where(go_left, packed[:, -2], packed[:, -1]) - 1
    return leaf_id, f_row, slot_row


def _histogram_rows(slot_row: Optional[jnp.ndarray], included: jnp.ndarray,
                    sampled: bool) -> Optional[jnp.ndarray]:
    """Every row's pending slot as the HISTOGRAM pass sees it: under a row
    sample an out-of-sample row is in no slot (-1), whatever its leaf. The
    routing pass moved it all the same."""
    if slot_row is None or not sampled:
        return slot_row
    return jnp.where(included > 0, slot_row, -1)


def _rows_by_slot(slot_row: jnp.ndarray, num_slots: int) -> jnp.ndarray:
    """Row numbers grouped by pending slot, ascending within a slot, the
    rows of no pending leaf last: the index a compacted pass reads, from ONE
    sort over the rows.

    Where the slot and the row number fit one int32 word (rows <= 2^24 on
    this device and slots < 2^7: a function of the shapes, not a knob) the
    keys ``slot << 24 | row`` are unique, so an unstable sort of that one
    operand returns exactly the stable order and moves half the bytes;
    beyond that it is the stable sort of (slot, row) pairs. At 14,680,064
    rows on the v5e: 0.0116 s against 0.0328 s, where one element gather
    over the rows costs 0.127 s (my chip run, PR 28)."""
    n = slot_row.shape[0]
    key = jnp.where(slot_row >= 0, slot_row, num_slots)
    rows = jax.lax.iota(jnp.int32, n)
    one_word = sort_is_one_word(n, num_slots)
    operands = ((key << 24) | rows,) if one_word else (key, rows)
    out = jax.lax.sort(operands, num_keys=1, is_stable=not one_word)
    return out[0] & ((1 << 24) - 1) if one_word else out[1]


def _slot_grouped_rows(slot_row: jnp.ndarray, num_slots: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """What a compacted pass reads besides the packed rows: (the row index
    grouped by pending slot, the rows of each slot), from every row's slot
    (-1: its leaf is not pending, or the row is outside the step's row
    sample). The counts sum to the pending rows."""
    row_idx = _rows_by_slot(slot_row, num_slots)
    counts = jnp.sum((slot_row[:, None]
                      == jnp.arange(num_slots, dtype=jnp.int32)[None, :])
                     .astype(jnp.int32), axis=0)
    return row_idx, counts


@trace_entry("grower.wave_body")
def grow_tree(
    X: jnp.ndarray,               # [N, F] bin codes ([N, G] bundled under EFB)
    grad: jnp.ndarray,            # [N] f32, bagging/padding-masked
    hess: jnp.ndarray,            # [N] f32
    included: jnp.ndarray,        # [N] f32 0/1
    feature_ok: jnp.ndarray,      # [F] bool: feature_fraction mask & non-trivial
    is_cat: jnp.ndarray,          # [F] bool: categorical feature
    num_bins: jnp.ndarray,        # [F] i32
    missing_code: jnp.ndarray,    # [F] i32
    default_bin: jnp.ndarray,     # [F] i32
    spec: GrowerSpec,
    comm=None,
    bundle: Optional[BundleDecode] = None,
    sampled: bool = False,
) -> Tuple[TreeArrays, jnp.ndarray, WaveStats]:
    """Grow one tree; returns (tree arrays, final leaf_id per row, the
    loop's own per-wave counters with a leading device axis of 1).

    ``sampled`` (static: the step draws a row sample, bagging or GOSS): the
    included rows are the histogram's row set. A row counts as pending for
    the HISTOGRAM only where ``included`` is set, so ``n_active``, the arm
    of the wave's ``cond`` and the compacted passes' row index leave the
    out-of-sample rows out (the reference hands its tree learner
    ``bag_data_indices``, goss.hpp / gbdt.cpp ``SetBaggingData``). ROUTING
    still moves every row every wave: ``leaf_id`` covers the out-of-sample
    rows, which is how they get their score (the reference's out-of-bag
    ``AddPredictionToScore``). A sampled tree also takes its leaf values
    from the rows' own sums at the end (``_leaf_values_from_rows``). Without
    it, and with ``spec.row_compact`` off, the program is the one it was:
    every row of a pending leaf is pending.

    With a distributed ``comm`` (parallel/comm.py) this body runs inside
    shard_map: X/grad/hess/leaf_id may be row-local shards, the histogram
    cache covers only this device's feature block, and split candidates are
    globally synced — the tree arrays stay replicated on every device.

    With ``bundle`` (EFB, efb.py), ``X`` holds bundled columns: histograms
    build + cache in bundle space ([.., G, hist_bins, ..]) and — on the
    native default — the split scan runs on bundle space directly, with
    only the winning splits translated back and row routing comparing the
    bundled code against the split's bundle range (spec.efb_unpack keeps
    the legacy unpack-before-scan arm). Tree arrays are ALWAYS in original
    feature space.
    """
    if comm is None:
        from .parallel.comm import SerialComm
        comm = SerialComm(spec.num_features)
    # without row compaction there is no row set to shrink: every pass
    # streams, out-of-sample rows with weight 0, as before
    sampled = sampled and spec.row_compact
    L = spec.num_leaves
    M = L - 1
    S = spec.hist_slots
    B = spec.num_bins_padded
    N = X.shape[0]
    X_hist = comm.hist_X(X)       # columns this device histograms
    F_hist = X_hist.shape[1]      # == F unless bundled (then G)
    # Width AFTER comm.reduce_hist: under data-parallel the psum_scatter
    # leaves each device only its F/D feature block (reference
    # data_parallel_tree_learner.cpp:148-163) — the per-leaf cache, sibling
    # subtraction, and split scan all live in that post-reduction space.
    #
    # EFB (native default): bundle space is the representation END-TO-END —
    # the histogram builds, caches, reduces, and SCANS as [.., G, Bb, ..]
    # (ops/split_finder.per_feature_best_bundled finds splits on bundled
    # bins directly, like the reference's FeatureGroup), and only the
    # <= wave_size winning splits translate back to (feature, bin). Under
    # data-parallel the psum_scatter therefore runs over bundle-COLUMN
    # blocks (DataParallelBundledComm — the collective shrinks from F*B to
    # G*Bb wide) and the scan localizes its code tables to the block.
    #
    # LEGACY arm (spec.efb_unpack, the A/B + parity pin): the scan unpacks
    # to original feature space — serial/bundled-block layouts at scan
    # time, row-sharded strategies BEFORE the collective using this shard's
    # leaf totals (feature blocks stay contiguous through the psum_scatter).
    unbundle_early = _unbundles_early(spec, comm, bundle is not None)
    scan_bundle = bundle
    if bundle is not None and getattr(comm, "bundled_blocks", False):
        scan_bundle = comm.localize_bundle(bundle)
    B_hist = spec.hist_bins or B  # bundle-space bin axis (build side)
    # the one-leaf form of the chunk matmul, where this build has one
    form = (one_leaf_form(F_hist, B_hist, spec.chunk_rows)
            if spec.one_leaf_frac > 0 else None)
    assert form is None or (spec.hist_kernel == "xla" and not spec.hist_f64), \
        "the one-leaf form stands beside the xla kernel's bf16 hi/lo mode"
    F_cache, B_cache = scan_hist_shape(spec, comm, F_hist, bundle is not None)
    bm = comm.block_meta(feature_ok, num_bins, missing_code, default_bin, is_cat)

    with jax.named_scope("tree.root_sums"):
        rg, rh, rc = comm.reduce_scalars(*root_sums(grad, hess, included))

    # one packed u8 row array per TREE (bin-code bytes + bf16 g/h channel
    # bytes): the compacted waves gather rows from it with a single random
    # access each (and the Pallas kernel reads every pass from it); building
    # it is an O(N) sequential write paid once here instead of per wave
    # weight-channel mode: hist_f64 carries full f32 channels (exact
    # products at Precision.HIGHEST + Kahan chunk carry in build_histograms).
    # Guard at the mechanism: the pallas kernel unpacks packed weights as
    # bf16 unconditionally, so f32-mode rows would silently decode garbage
    assert not (spec.hist_f64 and spec.hist_kernel in ("pallas", "mixed")), \
        "tpu_hist_f64 requires the xla histogram kernel"
    if spec.row_compact or spec.hist_kernel == "pallas":
        from .ops.histogram import pack_rows
        with jax.named_scope("tree.pack_rows"):
            packed_rows, _ = pack_rows(X_hist, grad, hess, included,
                                       spec.hist_f64, spec.code_mode)
    else:
        packed_rows = None

    tree = _empty_tree(L, B)
    state = GrowState(
        tree=tree,
        leaf_id=jnp.zeros(N, jnp.int32),
        hist=jnp.zeros((L + 1, F_cache, B_cache, 3), jnp.float32),
        sum_g=jnp.zeros(L + 1, jnp.float32).at[0].set(rg),
        sum_h=jnp.zeros(L + 1, jnp.float32).at[0].set(rh),
        cnt=jnp.zeros(L + 1, jnp.float32).at[0].set(rc),
        leaf_depth=jnp.zeros(L + 1, jnp.int32),
        leaf_is_right=jnp.zeros(L + 1, bool),
        cand=_empty_cand(L, B),
        needs_hist=jnp.zeros(L + 1, bool).at[0].set(True),
        sib_leaf=jnp.full(L + 1, L, jnp.int32),
        parent_cache=jnp.full(L + 1, L, jnp.int32),
        num_leaves_cur=jnp.asarray(1, jnp.int32),
        done=jnp.asarray(False),
        # the root is pending in slot 0, and every row the histogram
        # counts is in it
        slot_row=_histogram_rows(
            jnp.zeros(N, jnp.int32) if spec.row_compact else None,
            included, sampled),
        stats=_empty_stats(L, scan_block_pairs(S, F_cache, B_cache) < S,
                           form is not None),
    )

    leaf_iota = jnp.arange(L + 1, dtype=jnp.int32)

    def wave(state: GrowState) -> GrowState:
        # ---- 1. slot assignment for leaves needing histograms --------------
        with jax.named_scope("wave.slots"):
            pending = state.needs_hist
            slot_of_leaf, slot_rank = _slots_of(pending)              # [L+1]
            # leaf served by each slot (or L = scratch)
            leaf_of_slot = jnp.full(S, L, jnp.int32).at[
                jnp.where(pending, slot_rank, S)  # invalid -> dropped (S OOB)
            ].set(leaf_iota, mode="drop")

        # ---- 2. one masked pass builds S histograms ------------------------
        # then the distributed reduction: psum_scatter for data-parallel
        # (reference data_parallel_tree_learner.cpp:148-163), identity
        # otherwise; output covers this device's feature block only.
        def hist_pass(row_idx=None, n_active=None, slot_counts=None,
                      one_leaf=None):
            # "mixed": the XLA one-hot matmul for FULL streaming passes and
            # the Pallas VMEM-accumulator kernel for COMPACTED passes (which
            # kernel wins which pass type on today's chip: not measured,
            # ROADMAP Queue 1 item 7)
            use_pallas = (spec.hist_kernel == "pallas"
                          or (spec.hist_kernel == "mixed"
                              and row_idx is not None))
            if use_pallas:
                from .ops.pallas_histogram import build_histograms_pallas
                return build_histograms_pallas(
                    X_hist, grad, hess, included, state.leaf_id, slot_of_leaf,
                    num_slots=S, num_bins_padded=B_hist,
                    # mixed leaves spec.chunk_rows at the XLA path's large
                    # streaming chunk; the pallas grid step is its own knob
                    chunk_rows=min(spec.chunk_rows, 512),
                    row_idx=row_idx,
                    n_active=n_active, slot_counts=slot_counts,
                    packed=packed_rows,
                    # the adaptive cond only takes this path when
                    # n_active*4 < N — grid + buffers shrink to match
                    max_rows=(N + 3) // 4)
            return build_histograms(
                X_hist, grad, hess, included, state.leaf_id, slot_of_leaf,
                num_slots=S, num_bins_padded=B_hist, chunk_rows=spec.chunk_rows,
                row_idx=row_idx, n_active=n_active, exact=spec.hist_f64,
                slot_counts=slot_counts, packed=packed_rows,
                code_mode=spec.code_mode, compensated=spec.hist_f64,
                one_leaf=one_leaf)

        # A wave that holds ONE pending leaf (the root's pass, its smaller
        # child's) builds it in the one-leaf form where the build has one:
        # the count is the tree's own (replicated across shards under
        # tree_learner=data, so every chip takes the same form in the same
        # wave); which ARM a shard takes stays its own rows' business
        one_leaf = (jnp.sum(pending.astype(jnp.int32)) == 1
                    if form is not None else None)

        if spec.row_compact:
            # Adaptive, the TPU analog of the reference histogramming only
            # the smaller leaf's rows (serial_tree_learner.cpp:354-362): a
            # streamed pass pays the chunk matmul for every row of the
            # device, pending or not; a compacted pass pays it for the
            # pending rows only, plus one packed-row gather each and one
            # sort of the rows. spec.compact_frac is the pending share
            # where the two cost the same at this table's shape
            # (ops/histogram.compact_break_even; on the v5e 0.70 at 67
            # columns x 256 bins, 0.95 at 2,000, 0.26 at 28 columns and
            # 0.35 at 10: PERF.md, PR 31). An unsampled root has every row
            # pending and streams; a sampled one (bagging, GOSS) has the
            # included rows pending and compacts where their share is
            # under the threshold (0.30 < 0.70 at GOSS's documented rates
            # on 67 columns); a later wave
            # histograms smaller children, under half of the rows, and
            # compacts unless the table is narrow. Under tree_learner=data
            # N is a shard's rows and each shard decides for itself, inside
            # its own cond; the reduction is outside it.
            # the slot of every row's leaf came with the routing pass that
            # moved the row (step 8): one reduction decides the arm
            with jax.named_scope("wave.slots"):
                slot_row = state.slot_row                             # [N] i32
                n_active = jnp.sum((slot_row >= 0).astype(jnp.int32))

            def compact_pass():
                # rows grouped by slot, original order within a slot, built
                # here and now by one sort — only the waves that take this
                # arm pay for it, and nothing is carried
                with jax.named_scope("wave.partition"):
                    row_idx, counts = _slot_grouped_rows(slot_row, S)
                return hist_pass(row_idx, n_active, counts)

            # the threshold is a static Python int, so the predicate cannot
            # overflow int32 at any N. Pallas/mixed kernels keep the N/4
            # cap whatever the spec says: their skip-grid buffers are
            # provably sized by max_rows=(N+3)//4 (n_active < N//4).
            frac = spec.compact_frac
            if spec.hist_kernel in ("pallas", "mixed"):
                frac = min(frac, PALLAS_COMPACT_FRAC_CAP)
            compacted = n_active < int(N * frac)

            def compact_arm():
                with jax.named_scope("wave.hist.compact"):
                    return compact_pass()

            def stream_arm():
                with jax.named_scope("wave.hist.stream"):
                    return hist_pass()

            if form is None:
                new_hist = jax.lax.cond(compacted, compact_arm, stream_arm)
            else:
                # the one-leaf form has its own stream-or-compact threshold
                # (its matmul is cheaper, its gather and sort are not), and
                # its compacted pass reads the leaf's rows as the first
                # n_active entries of the sorted index: no per-slot counts
                compacted = jnp.where(
                    one_leaf, n_active < int(N * spec.one_leaf_frac),
                    compacted)

                def one_leaf_compact_arm():
                    with jax.named_scope("wave.hist.compact"):
                        with jax.named_scope("wave.partition"):
                            row_idx = _rows_by_slot(slot_row, S)
                        return hist_pass(row_idx, n_active, one_leaf=form)

                def one_leaf_stream_arm():
                    with jax.named_scope("wave.hist.stream"):
                        return hist_pass(one_leaf=form)

                new_hist = jax.lax.switch(
                    compacted.astype(jnp.int32)
                    + 2 * one_leaf.astype(jnp.int32),
                    (stream_arm, compact_arm,
                     one_leaf_stream_arm, one_leaf_compact_arm))
        else:
            n_active = jnp.asarray(-1, jnp.int32)   # not counted in this arm
            compacted = jnp.asarray(False)
            with jax.named_scope("wave.hist.stream"):
                if form is None:
                    new_hist = hist_pass()
                else:
                    new_hist = jax.lax.cond(
                        one_leaf, lambda: hist_pass(one_leaf=form),
                        lambda: hist_pass())
        with jax.named_scope("wave.hist.reduce"):
            if unbundle_early:
                # this shard's leaf totals: any bundled column's bins partition
                # the shard's included rows, so column 0's bin sums ARE them —
                # exactly what _unpack_bundled's FixHistogram-by-subtraction
                # needs for LOCAL histograms (global totals would mis-size the
                # reconstructed default bin before the psum)
                lpg = jnp.sum(new_hist[:, 0, :, 0], axis=-1)
                lph = jnp.sum(new_hist[:, 0, :, 1], axis=-1)
                lpc = jnp.sum(new_hist[:, 0, :, 2], axis=-1)
                new_hist = _unpack_bundled(new_hist, bundle, lpg, lph, lpc,
                                           default_bin)
            new_hist = comm.reduce_hist(new_hist)

        # ---- 3-6 + routing table: the shared wave tail ---------------------
        state2, table, map_mask, _n_apply, scan_slots = _apply_wave_splits(
            state, new_hist, leaf_of_slot, bm, spec, comm,
            scan_bundle if (bundle is not None and not unbundle_early)
            else None, num_bins, missing_code, default_bin,
            route_bundle=_route_bundle(spec, bundle))

        # ---- 7-8. route rows of split leaves; the same pass hands every row
        # the histogram slot of its leaf in the next wave (the pending
        # leaves are children of THIS wave's splits, so the slot rides in
        # the routing table beside the split: one lookup a row, a wave)
        leaf_id, f_row, slot_row_next = _route_rows(
            X, state.leaf_id, table, map_mask, spec, bundle, default_bin)

        # ---- 9. the loop's own counters, one entry per wave ----------------
        with jax.named_scope("wave.stats"):
            # rows of the leaves split this wave: a count over the routing
            # pass's output
            rows_split = jnp.sum((f_row >= 0).astype(jnp.int32))
            # slots of this wave's split scan that held a leaf: the pending
            # leaves, and those of their siblings that exist (the root has
            # none)
            held = leaf_of_slot < L
            scan_pending = jnp.sum(held.astype(jnp.int32)) + jnp.sum(
                (held & (state.sib_leaf[leaf_of_slot] < L)).astype(jnp.int32))
            st = state.stats
            stats = WaveStats(
                waves=st.waves + 1,
                rows_active=st.rows_active.at[st.waves].set(n_active),
                compacted=st.compacted.at[st.waves].set(compacted),
                rows_split=st.rows_split.at[st.waves].set(rows_split),
                scan_pending=st.scan_pending.at[st.waves].set(scan_pending),
                scan_slots=(None if scan_slots is None else
                            st.scan_slots.at[st.waves].set(scan_slots)),
                one_leaf=(None if one_leaf is None else
                          st.one_leaf.at[st.waves].set(one_leaf)))

        return state2._replace(
            leaf_id=leaf_id, stats=stats,
            slot_row=_histogram_rows(slot_row_next, included, sampled))

    def cond(state: GrowState):
        return ~state.done

    def body(state: GrowState):
        return wave(state)

    final = jax.lax.while_loop(cond, body, state)
    # Scratch rows (leaf L, internal M) accumulate masked-split garbage that
    # can be Inf/NaN (e.g. leaf_output with zero hessian). No row routes to
    # them, but table_lookup's one-hot contraction reads every table row
    # with weight 0 — and 0 * Inf = NaN. Zero them so downstream score
    # updates stay exact; legitimate leaves are untouched.
    tr = final.tree
    if sampled:
        tr = _leaf_values_from_rows(tr, final.leaf_id, grad, hess, comm, spec)
    tr = tr._replace(
        leaf_value=tr.leaf_value.at[L].set(0.0),
        internal_value=tr.internal_value.at[M].set(0.0))
    if counts_past_f32(N, comm):
        tr = _exact_counts(tr, final.leaf_id, included, comm, L)
    # the counters leave with a leading device axis: under shard_map each
    # device's own record is one row of the global array (comm.shard_grow)
    return tr, final.leaf_id, jax.tree.map(lambda a: a[None], final.stats)


@jax.named_scope("tree.leaf_sums")
def _leaf_values_from_rows(tree: TreeArrays, leaf_id, grad, hess, comm,
                           spec: GrowerSpec) -> TreeArrays:
    """The leaves' values taken again from the rows' own g and h, where the
    rows ended up. Growth takes a child's sums from its parent's histogram
    (a prefix sum, or the parent's total less one: float32 differences of
    sums as large as the table's), so a small leaf cut from a large node
    carries the large node's rounding: 7.8e-3 of a leaf value for a leaf of
    104 sampled rows (H = 32) under a node of H ~ 1e6, where rounding g and
    h to bfloat16 costs 4e-3 (my chip run, PR 36). Under a row sample a
    leaf's weight is a third of what its rows suggest at GOSS's rates, and
    such leaves turn up; so a sampled tree sums its included rows per leaf
    once more (``grad`` and ``hess`` are already masked and amplified), in
    chunks, each sum accurate to ITS OWN size, and derives the values from
    those. Splits, gains and counts stay the loop's."""
    L, ch = spec.num_leaves, spec.chunk_rows
    leaves = jnp.arange(L + 1, dtype=leaf_id.dtype)

    def chunk(acc, i):
        sl = jax.lax.dynamic_slice_in_dim
        onehot = (sl(leaf_id, i * ch, ch)[:, None] == leaves[None, :])
        gh = jnp.stack([sl(grad, i * ch, ch), sl(hess, i * ch, ch)], axis=1)
        return acc + jax.lax.dot_general(
            onehot.astype(jnp.float32), gh, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32), ()

    sums, _ = jax.lax.scan(chunk, jnp.zeros((L + 1, 2), jnp.float32),
                           jnp.arange(leaf_id.shape[0] // ch))
    (sums,) = comm.reduce_scalars(sums)
    value = leaf_output(sums[:, 0], sums[:, 1], spec.lambda_l1, spec.lambda_l2)
    # a tree that never split keeps its empty root
    live = (leaves < tree.num_leaves) & (tree.num_leaves > 1)
    return tree._replace(leaf_value=jnp.where(live, value, tree.leaf_value))


# the histograms' count channel, the leaves' running counts and the split
# finder's prefix sums are float32: whole numbers up to here, and no further
_F32_EXACT_ROWS = 1 << 24


def counts_past_f32(rows_per_device: int, comm) -> bool:
    """Whether a tree over this many rows a device can hold a node of more
    rows than float32 counts: the table's rows are the devices' together."""
    return rows_per_device * getattr(comm, "num_devices", 1) > _F32_EXACT_ROWS


@jax.named_scope("tree.exact_counts")
def _exact_counts(tree: TreeArrays, leaf_id, included, comm, L: int) -> TreeArrays:
    """The tree's row counts taken again, in int32, from where the rows
    ended up. A table of more than 2^24 rows (four workers' shares of the
    Criteo cell: 44,040,192) has nodes whose float32 counts are no longer
    whole: a bin that holds 17.6M rows rounds to an even number in the
    cross-device sum, the sibling's histogram inherits the error through
    the subtraction, and ``cnt - left`` hands it down to every larger child
    (119 nodes of four trees off by a row or two on the chip; PERF.md,
    PR 34). Each shard counts its in-bag rows per leaf (one compare-sum
    over its rows), the shards' counts are summed as integers, and a node's
    count is its children's: children are made after their parent, so one
    pass down the node numbers finds them counted. Growth itself still
    gates ``min_data_in_leaf`` on the float32 counts."""
    leaves = jnp.arange(L + 1, dtype=leaf_id.dtype)
    mine = jnp.sum(((leaf_id[:, None] == leaves[None, :])
                    & (included[:, None] > 0)).astype(jnp.int32), axis=0)
    (leaf_count,) = comm.reduce_scalars(mine)                     # [L+1] i32
    leaf_count = leaf_count.at[L].set(0)

    def count_of(child, internal_count):
        return jnp.where(child < 0, leaf_count[jnp.clip(-child - 1, 0, L)],
                         internal_count[jnp.clip(child, 0, L - 1)])

    def node(k, internal_count):
        nid = L - 2 - k
        both = (count_of(tree.left_child[nid], internal_count)
                + count_of(tree.right_child[nid], internal_count))
        return internal_count.at[nid].set(
            jnp.where(nid < tree.num_leaves - 1, both, 0))

    internal_count = jax.lax.fori_loop(
        0, L - 1, node, jnp.zeros(L, jnp.int32))
    return tree._replace(leaf_count=leaf_count, internal_count=internal_count)


# ======================================================================
# Out-of-core streamed growth (tpu_residency=stream; ops/stream.py)
# ======================================================================

@trace_entry("grower.stream_legs")
class StreamedGrower:
    """Host-driven out-of-core twin of :func:`grow_tree`.

    The resident grower is ONE jitted while_loop over waves with the whole
    code matrix in HBM. Here the packed bin codes live in host-resident
    row shards (ops/stream.py HostShardStore) and each wave makes one pass
    over them:

    - a per-shard jitted ``shard_pass`` first routes the shard's rows by
      the PREVIOUS wave's split table (so routing and the histogram read
      share one H2D transfer of the shard), then folds the shard's chunk
      partials into the carried accumulator via ``build_histograms``'s
      ``acc_init`` thread — the identical chunk-add sequence the resident
      full pass produces, so streamed training is BIT-identical to
      ``tpu_residency=device`` with ``tpu_row_compact=false``;
    - a once-per-wave jitted ``wave_update`` reduces the accumulator
      (``comm.reduce_hist`` — the same collective call site) and applies
      splits through the SAME :func:`_apply_wave_splits` the resident wave
      body uses.

    Per-row training state (leaf_id) and the split tables stay
    device-resident; ONLY the compressed bin codes stream H2D (arXiv
    1806.11248's design point), double-buffered so shard i+1's copy
    overlaps shard i's compute (arXiv 2005.09148). The prefetcher's device
    buffers are deliberately NEVER donated to any jitted fn — donation
    would let XLA scribble over a buffer the prefetcher may still hand
    out, so only the carried (acc, comp, leaf_id) ping-pong via
    ``donate_argnums``.

    The host drives the wave loop, so it fetches one (done, n_apply)
    scalar pair per wave — the streamed analog of the resident loop's
    device-side cond, and the one audited host sync. Every jitted fn here
    is shape-stable across waves, trees, and iterations: steady-state
    streamed waves add ZERO jit cache misses (pinned by
    tests/test_stream.py under RecompileGuard).

    Distributed (tree_learner=data|voting): the jitted legs run under
    shard_map with the resident specs — rows row-sharded, split state
    replicated — and the host store interleaves shards so device d always
    receives the SAME rows it would hold resident (ops/stream.py
    HostShardStore block layout); the per-device fold order is therefore
    unchanged and the identity extends to multi-chip training.
    """

    def __init__(self, spec: GrowerSpec, pctx, comm, *, n_rows_padded: int,
                 local_shard_rows: int, n_shards: int, num_cols: int,
                 code_mode: str, num_bins, missing_code, default_bin,
                 is_cat, bundle: Optional[BundleDecode] = None):
        self.spec = spec
        self.pctx = pctx
        self.comm = comm
        self.bundle = bundle
        self.n_rows_padded = n_rows_padded
        self.local_shard_rows = local_shard_rows   # rows per shard PER DEVICE
        self.n_shards = n_shards
        self.num_cols = num_cols                   # unpacked code-matrix width
        self.code_mode = code_mode
        self.num_bins = num_bins
        self.missing_code = missing_code
        self.default_bin = default_bin
        self.is_cat = is_cat
        # serial comm when none supplied (mirrors grow_tree)
        if comm is None:
            from .parallel.comm import SerialComm
            self.comm = SerialComm(spec.num_features)
        # EFB placement mirrors grow_tree: the native default scans bundle
        # space end-to-end (data-parallel reduces bundle-column blocks);
        # the legacy unpack arm (spec.efb_unpack) unpacks BEFORE the
        # collective under row-sharded strategies, at scan time serially
        self.unbundle_early = _unbundles_early(spec, self.comm,
                                               bundle is not None)
        assert pctx is None or pctx.strategy != "feature", \
            "streamed growth does not run under feature-parallel bundling"
        self._mesh = pctx.mesh if pctx is not None else None
        self._n_dev = pctx.num_devices if self._mesh is not None else 1
        from .ops.histogram import num_channels
        self._ch = num_channels(spec.hist_f64)
        self._B_hist = spec.hist_bins or spec.num_bins_padded
        self._build_fns()

    # ------------------------------------------------------------ jitted fns

    def _wrap(self, fn, in_specs, out_specs, donate=()):
        """shard_map under a mesh (resident specs), plain fn otherwise —
        then jit with donation (skipped on CPU, which ignores it loudly)."""
        if self._mesh is not None:
            fn = jax.shard_map(fn, mesh=self._mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        if self.pctx is not None and \
                self.pctx.devices[0].platform == "cpu":
            donate = ()
        return jax.jit(fn, donate_argnums=donate)

    def _build_fns(self):
        spec = self.spec
        comm = self.comm
        L = spec.num_leaves
        M = L - 1
        S = spec.hist_slots
        B = spec.num_bins_padded
        B_hist = self._B_hist
        ch = self._ch
        Rd = self.local_shard_rows
        F_cols = self.num_cols
        D = self._n_dev
        bundle = self.bundle
        from jax.sharding import PartitionSpec as P
        axis = self.pctx.ROW_AXIS if self._mesh is not None else None
        rows = P(axis) if axis else None
        rows2d = P(axis, None) if axis else None
        accs = P(axis, None, None, None) if axis else None
        repl = P() if axis else None
        from .ops.histogram import (build_histograms, finalize_histograms,
                                    unpack_codes)

        F_cache, B_cache = scan_hist_shape(spec, comm, F_cols,
                                           bundle is not None)

        def init_body(grad, hess, included):
            rg, rh, rc = comm.reduce_scalars(
                *root_sums(grad, hess, included))
            n_local = grad.shape[0]
            state = GrowState(
                tree=_empty_tree(L, B),
                leaf_id=jnp.zeros((), jnp.int32),   # per-row leaf_id is
                                                    # carried SEPARATELY
                hist=jnp.zeros((L + 1, F_cache, B_cache, 3), jnp.float32),
                sum_g=jnp.zeros(L + 1, jnp.float32).at[0].set(rg),
                sum_h=jnp.zeros(L + 1, jnp.float32).at[0].set(rh),
                cnt=jnp.zeros(L + 1, jnp.float32).at[0].set(rc),
                leaf_depth=jnp.zeros(L + 1, jnp.int32),
                leaf_is_right=jnp.zeros(L + 1, bool),
                cand=_empty_cand(L, B),
                needs_hist=jnp.zeros(L + 1, bool).at[0].set(True),
                sib_leaf=jnp.full(L + 1, L, jnp.int32),
                parent_cache=jnp.full(L + 1, L, jnp.int32),
                num_leaves_cur=jnp.asarray(1, jnp.int32),
                done=jnp.asarray(False),
            )
            leaf_id = jnp.zeros(n_local, jnp.int32)
            # wave-1 routing table: no ordinal carries a key -> identity
            # route. Width must match what _apply_wave_splits emits for
            # THIS arm (route_table_cols) — a narrower wave-1 table would
            # both re-trace shard_fn/route_fn against the streamed
            # shape-stability contract and lean on JAX's silent
            # out-of-bounds clamp for the bundle columns
            table0 = empty_route_table(spec, bundle)
            map_mask0 = (jnp.zeros((L + 1, B), bool)
                         if spec.use_categorical else None)
            return state, leaf_id, table0, map_mask0

        self.init_fn = self._wrap(
            init_body, in_specs=(rows, rows, rows),
            out_specs=(repl, rows, repl, repl))

        def slot_body(needs_hist):
            # step 1 of the resident wave, verbatim
            leaf_iota = jnp.arange(L + 1, dtype=jnp.int32)
            pending = needs_hist
            slot_of_leaf, slot_rank = _slots_of(pending)
            leaf_of_slot = jnp.full(S, L, jnp.int32).at[
                jnp.where(pending, slot_rank, S)
            ].set(leaf_iota, mode="drop")
            return slot_of_leaf, leaf_of_slot

        self.slot_fn = jax.jit(slot_body)

        def zeros_body():
            acc = jnp.zeros((D, F_cols, B_hist, S * ch), jnp.float32)
            comp = (jnp.zeros_like(acc) if spec.hist_f64
                    else jnp.zeros((D,), jnp.float32))
            return acc, comp

        # fresh accumulator buffers each wave: (acc, comp) are DONATED into
        # every shard_pass, so a cached zero array would be written over
        self.zeros_fn = self._wrap(zeros_body, in_specs=(),
                                   out_specs=(accs, accs if spec.hist_f64
                                              else rows))

        def shard_body(acc, comp, codes_sh, leaf_id, g, h, m,
                       slot_of_leaf, table, map_mask, i):
            start = i * Rd
            lid_sh = jax.lax.dynamic_slice_in_dim(leaf_id, start, Rd)
            codes = unpack_codes(codes_sh, F_cols, self.code_mode)
            # route by the PREVIOUS wave's table first (wave 1 arrives with
            # the inert table): one shard transfer serves both legs
            new_lid, _, _ = _route_rows(codes, lid_sh, table, map_mask,
                                        spec, bundle, self.default_bin)
            leaf_id = jax.lax.dynamic_update_slice_in_dim(
                leaf_id, new_lid, start, 0)
            g_sh = jax.lax.dynamic_slice_in_dim(g, start, Rd)
            h_sh = jax.lax.dynamic_slice_in_dim(h, start, Rd)
            m_sh = jax.lax.dynamic_slice_in_dim(m, start, Rd)
            acc_l = acc[0]
            acc_l, comp_l = build_histograms(
                codes, g_sh, h_sh, m_sh, new_lid, slot_of_leaf,
                num_slots=S, num_bins_padded=B_hist,
                chunk_rows=spec.chunk_rows, exact=spec.hist_f64,
                compensated=spec.hist_f64, acc_init=acc_l,
                comp_init=comp[0] if spec.hist_f64 else None,
                raw_output=True)
            if not spec.hist_f64:
                comp_l = jnp.zeros((), jnp.float32)
            return acc_l[None], comp_l[None], leaf_id

        self.shard_fn = self._wrap(
            shard_body,
            in_specs=(accs, accs if spec.hist_f64 else rows, rows2d, rows,
                      rows, rows, rows, repl, repl, repl, repl),
            out_specs=(accs, accs if spec.hist_f64 else rows, rows),
            donate=(0, 1, 3))

        def wave_body(state, acc, leaf_of_slot, feature_ok):
            bm = comm.block_meta(feature_ok, self.num_bins,
                                 self.missing_code, self.default_bin,
                                 self.is_cat)
            new_hist = finalize_histograms(acc[0], S, spec.hist_f64)
            if self.unbundle_early:
                lpg = jnp.sum(new_hist[:, 0, :, 0], axis=-1)
                lph = jnp.sum(new_hist[:, 0, :, 1], axis=-1)
                lpc = jnp.sum(new_hist[:, 0, :, 2], axis=-1)
                new_hist = _unpack_bundled(new_hist, bundle, lpg, lph, lpc,
                                           self.default_bin)
            new_hist = comm.reduce_hist(new_hist)
            scan_bundle = None
            if bundle is not None and not self.unbundle_early:
                scan_bundle = (comm.localize_bundle(bundle)
                               if getattr(comm, "bundled_blocks", False)
                               else bundle)
            state2, table, map_mask, n_apply, _ = _apply_wave_splits(
                state, new_hist, leaf_of_slot, bm, spec, comm, scan_bundle,
                self.num_bins, self.missing_code, self.default_bin,
                route_bundle=_route_bundle(spec, bundle))
            return state2, table, map_mask, state2.done, n_apply

        self.wave_fn = self._wrap(
            wave_body, in_specs=(repl, accs, repl, repl),
            out_specs=(repl, repl, repl, repl, repl))

        def route_body(codes_sh, leaf_id, table, map_mask, i):
            # trailing routing pass: the final wave applied splits the next
            # hist pass will never run for — rows still must reach them
            start = i * Rd
            lid_sh = jax.lax.dynamic_slice_in_dim(leaf_id, start, Rd)
            codes = unpack_codes(codes_sh, F_cols, self.code_mode)
            new_lid, _, _ = _route_rows(codes, lid_sh, table, map_mask,
                                        spec, bundle, self.default_bin)
            return jax.lax.dynamic_update_slice_in_dim(
                leaf_id, new_lid, start, 0)

        self.route_fn = self._wrap(
            route_body, in_specs=(rows2d, rows, repl, repl, repl),
            out_specs=rows, donate=(1,))

        def finalize_body(tree):
            # scratch-row zeroing, exactly as grow_tree's loop exit
            return tree._replace(
                leaf_value=tree.leaf_value.at[L].set(0.0),
                internal_value=tree.internal_value.at[M].set(0.0))

        self.finalize_fn = jax.jit(finalize_body)

    # ------------------------------------------------------------- host loop

    def jit_entrypoints(self):
        """(name, jitted fn) pairs for RecompileGuard registration."""
        return [("stream.init", self.init_fn), ("stream.slot", self.slot_fn),
                ("stream.zeros", self.zeros_fn),
                ("stream.shard_pass", self.shard_fn),
                ("stream.wave_update", self.wave_fn),
                ("stream.route", self.route_fn),
                ("stream.finalize", self.finalize_fn)]

    @allowed_host_sync("streamed wave loop: one (done, n_apply) scalar "
                       "pair per wave — the host drives the wave loop in "
                       "stream mode")
    def _fetch_wave_flags(self, done, n_apply):
        """One (done, n_apply) scalar fetch per wave — the host-driven
        loop's termination test (the streamed analog of the resident
        while_loop cond). Wrapped so the sync point is a single audited
        site."""
        d, n = jax.device_get((done, n_apply))
        return bool(d), int(n)

    def grow(self, stream, grad, hess, included, feature_ok):
        """Grow one tree over the streamed shards; returns
        ``(tree arrays, final leaf_id per row)`` exactly like grow_tree.
        ``stream`` is an ops/stream.ShardPrefetcher over the booster's
        HostShardStore; grad/hess/included are the bagging-masked per-row
        arrays (device-resident throughout)."""
        from .observability import costs as obs_costs
        state, leaf_id, table, map_mask = self.init_fn(grad, hess, included)
        cost_dims = dict(rows_padded=int(self.n_rows_padded),
                         n_shards=int(self.n_shards),
                         shard_rows=int(self.local_shard_rows * self._n_dev),
                         features=int(self.num_cols),
                         hist_slots=int(self.spec.hist_slots),
                         residency="stream")
        while True:
            slot_of_leaf, leaf_of_slot = self.slot_fn(state.needs_hist)
            acc, comp = self.zeros_fn()
            for i in range(self.n_shards):
                codes = stream.get(i)
                if obs_costs.enabled():
                    # per-shard cost leg of the dispatch protocol — capture
                    # dedupes on the callable, so this is a no-op after
                    # the first wave (compile-time only, no recompile)
                    obs_costs.capture_jit(
                        "train_step.stream.shard_pass", self.shard_fn,
                        args=(acc, comp, codes, leaf_id, grad, hess,
                              included, slot_of_leaf, table, map_mask,
                              np.int32(i)), dims=cost_dims)
                acc, comp, leaf_id = self.shard_fn(
                    acc, comp, codes, leaf_id, grad, hess, included,
                    slot_of_leaf, table, map_mask, np.int32(i))
                # issue shard i+1's H2D while the device chews shard i
                stream.prefetch(i + 1)
            if obs_costs.enabled():
                obs_costs.capture_jit(
                    "train_step.stream.wave_update", self.wave_fn,
                    args=(state, acc, leaf_of_slot, feature_ok),
                    dims=cost_dims)
            state, table, map_mask, done, n_apply = self.wave_fn(
                state, acc, leaf_of_slot, feature_ok)
            done_h, n_apply_h = self._fetch_wave_flags(done, n_apply)
            if done_h:
                if n_apply_h:
                    for i in range(self.n_shards):
                        codes = stream.get(i)
                        leaf_id = self.route_fn(codes, leaf_id, table,
                                                map_mask, np.int32(i))
                        stream.prefetch(i + 1)
                break
        return self.finalize_fn(state.tree), leaf_id
