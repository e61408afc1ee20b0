"""Communication strategies for distributed tree growth.

Reference counterparts (all re-expressed as XLA collectives over a mesh axis
instead of socket/MPI calls — SURVEY.md §2.6):

- ``DataParallelComm``   = DataParallelTreeLearner
  (src/treelearner/data_parallel_tree_learner.cpp): rows sharded across
  devices; local histograms for ALL features are `psum_scatter`-reduced so
  each device owns the globally-summed histograms of one feature block
  (:148-163), finds best splits on its block, and the global best is an
  all-gather + argmax (SyncUpGlobalBestSplit, parallel_tree_learner.h:184-207).
- ``FeatureParallelComm`` = FeatureParallelTreeLearner
  (src/treelearner/feature_parallel_tree_learner.cpp): every device holds all
  rows; features are block-partitioned (:31-50); each device histograms only
  its block and the winner is all-gather + argmax'd. No row sync needed —
  all devices route rows identically afterwards.
- ``VotingParallelComm`` = VotingParallelTreeLearner (PV-Tree,
  src/treelearner/voting_parallel_tree_learner.cpp): rows sharded; each
  device votes for its local top-k features per leaf (:317-332), votes are
  summed globally (GlobalVoting :165), and only the ~2k winning features'
  histogram columns are psum'd (CopyLocalHistogram :197) before the final
  scan — trading a little accuracy risk for O(k/F) communication.

Each Comm object is a *static* bundle of callables closed over the mesh axis
name; `grow_tree` (grower.py) calls them at trace time inside `shard_map`.

Per-row state under row-sharded strategies (data/voting): `leaf_id` and the
row's next-wave slot (GrowState.slot_row) are SHARD-LOCAL over this device's
row block. No collective ever touches them: a compacted wave's sort, slot
counts and packed-row gather all run on local rows, and each shard decides
stream-or-compact for itself, as the reference keeps one DataPartition per
machine over its local partition (data_parallel_tree_learner.cpp uses the
local data_partition_ for histogram construction). Split decisions arrive
replicated (the all-gather argmax below), so every shard routes consistently.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.categorical import per_feature_best_categorical
from ..ops.split_finder import (PerFeatureBest, SplitCandidates,
                                per_feature_best_bundled,
                                per_feature_best_numerical, reduce_features,
                                unpack_bundled_hist)


class BlockMeta(NamedTuple):
    """Per-feature metadata of the feature block this device scans.

    Arrays are [F_block]; ``offset`` maps local block index -> global feature
    index (a traced scalar: axis_index * F_block for sharded strategies).
    """
    feature_ok: jnp.ndarray
    num_bins: jnp.ndarray
    missing_code: jnp.ndarray
    default_bin: jnp.ndarray
    is_cat: jnp.ndarray
    offset: jnp.ndarray


def block_per_feature(hist, pg, ph, pc, bm: BlockMeta, spec, bundle=None):
    """Best split per (slot, feature) over this block: numerical scan for
    non-categorical features, categorical one-hot/sorted-prefix for the rest
    (reference FindBestThreshold dispatch, feature_histogram.hpp:72-104).
    Returns (PerFeatureBest, cat_mask [S, F, B] or None).

    With ``bundle`` (grower.BundleDecode — the native EFB arm) ``hist`` is
    BUNDLE-space [S, G, Bb, 3] and the numerical scan runs on it directly
    (per_feature_best_bundled, the reference's FeatureGroup discipline);
    categorical features keep the feature-space sorted-prefix search, fed
    by an unpack RESTRICTED to the categorical members' bundle columns
    (``spec.cat_features``, static at setup — the cat scan is per-feature
    independent, so the subset values are bit-identical to a full unpack
    without re-paying the [T, F, B, 3] decode the redesign deleted).
    """
    if bundle is not None:
        pf = per_feature_best_bundled(
            hist, pg, ph, pc, bm.num_bins, bm.missing_code, bm.default_bin,
            bm.feature_ok & ~bm.is_cat, bundle.col, bundle.lo, bundle.hi,
            bundle.off, bundle.code_feat, **spec.hyperparams())
        if not spec.use_categorical or not spec.cat_features:
            return pf, None
        ci = jnp.asarray(spec.cat_features, jnp.int32)
        hist_c = unpack_bundled_hist(
            hist, bundle.col[ci], bundle.unpack_bin[ci],
            pg, ph, pc, bm.default_bin[ci])             # [T, Fc, B, 3]
        pf_cat, mask_c = per_feature_best_categorical(
            hist_c, pg, ph, pc, bm.num_bins[ci], bm.missing_code[ci],
            (bm.feature_ok & bm.is_cat)[ci], **spec.hyperparams(),
            **spec.cat_hyperparams())
        # scatter the cat subset back into full feature width (cat_idx
        # positions ARE the is_cat positions, so this equals the full-width
        # where(is_cat, cat, numerical) merge bit-for-bit)
        merged = PerFeatureBest(*[
            nv.at[:, ci].set(cv) for nv, cv in zip(pf, pf_cat)])
        T, B = hist.shape[0], spec.num_bins_padded
        F = bm.num_bins.shape[0]
        mask = jnp.zeros((T, F, B), bool).at[:, ci].set(mask_c)
        return merged, mask
    pf = per_feature_best_numerical(
        hist, pg, ph, pc, bm.num_bins, bm.missing_code, bm.default_bin,
        bm.feature_ok & ~bm.is_cat, **spec.hyperparams())
    if not spec.use_categorical:
        return pf, None
    pf_cat, mask = per_feature_best_categorical(
        hist, pg, ph, pc, bm.num_bins, bm.missing_code,
        bm.feature_ok & bm.is_cat, **spec.hyperparams(),
        **spec.cat_hyperparams())
    merged = PerFeatureBest(*[
        jnp.where(bm.is_cat[None, :], cv, nv) for nv, cv in zip(pf, pf_cat)])
    return merged, mask


def find_block_splits(hist, pg, ph, pc, bm: BlockMeta, spec,
                      bundle=None) -> SplitCandidates:
    """Best split per slot over this block's features (feature argmax)."""
    pf, mask = block_per_feature(hist, pg, ph, pc, bm, spec, bundle)
    # candidate cat_mask stays ORIGINAL-bin-space wide even when the scan
    # ran on bundle space (the [L+1, B] routing mask consumes it)
    nb_pad = spec.num_bins_padded if bundle is not None else hist.shape[2]
    if mask is None:
        return reduce_features(pf, bm.offset, num_bins_padded=nb_pad)
    return reduce_features(pf, bm.offset, is_cat=bm.is_cat, cat_mask=mask)


# ---- a wave's split search in two halves -----------------------------------
# The grower finds a wave's splits as ``sync_splits(scan_block(...))``:
#   scan_block   the scan of this device's block, slot by slot, NO collective:
#                the slot axis is a batch axis, so the grower may run it over
#                a few slots at a time (grower._apply_wave_splits' blocks)
#   sync_splits  what crosses devices, always over all 2 x hist_slots slots at
#                once: the candidates' all-gather argmax, PV-Tree's votes and
#                the psum of the voted columns (``pick_cols(cols [T, k]) ->
#                [T, k, B, 3]`` hands it those columns of each slot's local
#                histogram, from wherever the caller keeps them)
# Both halves carry a ``gain`` leaf; a slot nobody scanned holds -inf there
# and zeros elsewhere, and loses every argmax and every vote.


class LocalGains(NamedTuple):
    """VotingParallelComm.scan_block's result: the local scan's best gain per
    (slot, feature), before any vote."""
    gain: jnp.ndarray             # f32 [T, F]


# serialized size of one slot's SplitCandidates leaves (the all-gather
# argmax payload): gain/left_g/left_h/left_c f32 + feature/threshold i32 +
# default_left/is_cat bool + the [B] bool cat_mask — the analog of the
# reference's serialized SplitInfo (split_info.hpp Size()). The cat_mask
# only travels when categorical splits are possible: without them it is a
# constant-zero array XLA folds out of the collective entirely (the round-6
# measured-HLO validation caught the always-charged mask overestimating the
# common numerical-only payload ~11x).
def _split_candidate_bytes(num_bins_padded: int,
                           use_categorical: bool = True) -> int:
    return 4 * 4 + 2 * 4 + 2 + (num_bins_padded if use_categorical else 0)


def _gather_argmax(cand: SplitCandidates, axis_name: str) -> SplitCandidates:
    """Global best split across devices: all-gather candidates, argmax on
    gain (reference SyncUpGlobalBestSplit, parallel_tree_learner.h:184-207 —
    there an Allreduce with a custom max-reducer over serialized SplitInfo).

    Ties resolve to the lowest device index; with features block-partitioned
    contiguously this equals the serial learner's lowest-feature-index rule.
    """
    with jax.named_scope("wave.split.allgather"):
        g = jax.lax.all_gather(cand, axis_name)      # leaves [D, S, ...]
    d_idx = jnp.argmax(g.gain, axis=0)               # [S]

    def pick(arr):
        idx = d_idx.reshape((1,) + d_idx.shape + (1,) * (arr.ndim - 2))
        return jnp.take_along_axis(arr, idx, axis=0)[0]

    return jax.tree.map(pick, g)


@dataclass(frozen=True)
class SerialComm:
    """Single-shard no-op strategy (reference SerialTreeLearner)."""
    num_features: int = 0            # F_hist == F_block (set by caller)

    def reduce_scalars(self, *xs):
        return xs

    def hist_X(self, X):
        """The columns this device histograms (all of them)."""
        return X

    def reduce_hist(self, hist):
        """[S, F_hist, B, 3] partial -> [S, F_block, B, 3] global sums."""
        return hist

    def reduced_hist_features(self, F_hist: int) -> int:
        """Feature width of ``reduce_hist``'s output — what the grower's
        per-leaf histogram cache must be sized by (identity here)."""
        return F_hist

    def block_meta(self, feature_ok, num_bins, missing_code, default_bin,
                   is_cat) -> BlockMeta:
        return BlockMeta(feature_ok, num_bins, missing_code, default_bin,
                         is_cat, jnp.asarray(0, jnp.int32))

    def scan_block(self, hist, pg, ph, pc, bm: BlockMeta, spec, bundle=None):
        """This device's scan of ``hist`` [T, F_block, B, 3], slot by slot."""
        return find_block_splits(hist, pg, ph, pc, bm, spec, bundle)

    def sync_splits(self, local, pick_cols, pg, ph, pc, bm: BlockMeta, spec,
                    bundle=None) -> SplitCandidates:
        """The devices' scans -> every slot's global best split."""
        return local

    def collective_bytes(self, num_slots: int, num_bins_padded: int,
                         use_categorical: bool = True,
                         hist_bins: int = None) -> dict:
        """Per-wave collective payload estimate in bytes, by collective —
        the MULTICHIP cost story (observability/costs.py publishes these as
        ``comm.bytes_per_wave.*`` gauges at booster construction).
        ``hist_bins`` is the bin width of the histograms the wave actually
        moves: bundle space (Bb) on the native EFB arm, original feature
        space otherwise — charging feature-space widths for a bundled run
        overstated every histogram collective. Serial runs none."""
        return {}


# of the ``collective_bytes`` entries, those a tree pays once (its root
# sums) and not once a wave
PER_TREE_COLLECTIVES = ("psum_root_scalars", "psum_leaf_counts")


def tree_collective_bytes(per_wave: dict, waves: int) -> dict:
    """Bytes one tree of ``waves`` waves moved through each collective of a
    ``collective_bytes`` estimate, and how many collectives it ran."""
    moved = {name: nbytes * (1 if name in PER_TREE_COLLECTIVES else waves)
             for name, nbytes in per_wave.items()}
    calls = sum(1 if name in PER_TREE_COLLECTIVES else waves
                for name in per_wave)
    return {"bytes": moved, "collectives": calls}


def _block_slice(arr, axis_index, block: int):
    return jax.lax.dynamic_slice_in_dim(arr, axis_index * block, block)


@dataclass(frozen=True)
class DataParallelComm:
    """Rows sharded on `axis`; histogram psum_scatter over feature blocks."""
    axis: str
    num_devices: int
    num_features: int                # padded: divisible by num_devices

    @property
    def block(self) -> int:
        return self.num_features // self.num_devices

    def reduce_scalars(self, *xs):
        return tuple(jax.lax.psum(x, self.axis) for x in xs)

    def hist_X(self, X):
        return X                      # all features, local rows

    def reduce_hist(self, hist):
        # [S, F, B, 3] local sums -> [S, F/D, B, 3] global sums of my block
        # (reference ReduceScatter of HistogramBinEntry,
        #  data_parallel_tree_learner.cpp:148-163)
        S, F, B, C = hist.shape
        D = self.num_devices
        blocks = hist.reshape(S, D, self.block, B, C)
        blocks = jnp.moveaxis(blocks, 1, 0)           # [D, S, F/D, B, C]
        return jax.lax.psum_scatter(blocks, self.axis, scatter_dimension=0,
                                    tiled=False)

    def reduced_hist_features(self, F_hist: int) -> int:
        # psum_scatter leaves each device holding only its feature block —
        # the cache must be block-shaped (each rank owns its block,
        # reference data_parallel_tree_learner.cpp:148-163)
        return self.block

    def block_meta(self, feature_ok, num_bins, missing_code, default_bin,
                   is_cat) -> BlockMeta:
        i = jax.lax.axis_index(self.axis)
        b = self.block
        return BlockMeta(
            _block_slice(feature_ok, i, b), _block_slice(num_bins, i, b),
            _block_slice(missing_code, i, b), _block_slice(default_bin, i, b),
            _block_slice(is_cat, i, b), i * b)

    scan_block = SerialComm.scan_block

    def sync_splits(self, local, pick_cols, pg, ph, pc, bm: BlockMeta, spec,
                    bundle=None) -> SplitCandidates:
        return _gather_argmax(local, self.axis)

    def collective_bytes(self, num_slots: int, num_bins_padded: int,
                         use_categorical: bool = True,
                         hist_bins: int = None) -> dict:
        """Data-parallel pays the full-width histogram reduce-scatter every
        wave (the reference's ReduceScatter of HistogramBinEntry,
        data_parallel_tree_learner.cpp:148-163) plus the candidate
        all-gather and one 3-scalar root psum per tree. This class only
        serves UNBUNDLED (or legacy early-unpacked EFB) runs, so the
        reduce-scatter is feature-space wide by construction; the native
        bundled run's shrunken collective lives on
        DataParallelBundledComm.

        The reduce-scatter covers the ``num_slots`` freshly-built
        histograms (siblings derive locally by subtraction); the candidate
        all-gather carries ``2 * num_slots`` rows — the split scan runs
        over slot+sibling pairs (grower.py step 4 concatenates them), which
        the round-6 measured-HLO validation (bench.py --multichip) pinned
        after the original estimate undercounted by exactly 2x."""
        scan_slots = 2 * num_slots
        return {
            "psum_root_scalars": 3 * 4,
            "psum_scatter_hist": (num_slots * self.num_features
                                  * num_bins_padded * 3 * 4),
            "allgather_splits": (self.num_devices * scan_slots
                                 * _split_candidate_bytes(num_bins_padded,
                                         use_categorical)),
        }


@dataclass(frozen=True)
class FeatureParallelComm:
    """Rows replicated; each device histograms one feature block."""
    axis: str
    num_devices: int
    num_features: int                # padded: divisible by num_devices

    @property
    def block(self) -> int:
        return self.num_features // self.num_devices

    def reduce_scalars(self, *xs):
        return xs                     # rows replicated -> sums already global

    def hist_X(self, X):
        i = jax.lax.axis_index(self.axis)
        return jax.lax.dynamic_slice_in_dim(X, i * self.block, self.block, axis=1)

    def reduce_hist(self, hist):
        return hist                   # [S, F/D, B, 3] already global

    reduced_hist_features = SerialComm.reduced_hist_features
    block_meta = DataParallelComm.block_meta
    scan_block = SerialComm.scan_block
    sync_splits = DataParallelComm.sync_splits

    def collective_bytes(self, num_slots: int, num_bins_padded: int,
                         use_categorical: bool = True,
                         hist_bins: int = None) -> dict:
        """Feature-parallel never moves histograms — rows are replicated,
        so the only wave collective is the candidate all-gather (over the
        2*num_slots slot+sibling scan rows, like DataParallelComm)."""
        return {
            "allgather_splits": (self.num_devices * 2 * num_slots
                                 * _split_candidate_bytes(num_bins_padded,
                                         use_categorical)),
        }


@dataclass(frozen=True)
class FeatureParallelBundledComm:
    """Feature-parallel under EFB: BUNDLED COLUMNS are the partitioned unit.

    The reference's feature-parallel learner partitions the dataset's
    post-EFB feature groups across machines (feature groups ARE the storage
    unit there, feature_parallel_tree_learner.cpp:31-50 over
    Dataset::FeatureGroup columns) — partitioning raw features here would
    tear bundles apart. Each device slices its block of bundled columns,
    histograms + caches in bundle space (sibling subtraction is linear, so
    it commutes with the unpack), and scans only its bundles' member
    features: ``block_meta`` masks ``feature_ok`` to the owned members and
    the candidates stay full-width / offset-0, so the usual all-gather
    argmax (SyncUpGlobalBestSplit) is unchanged. Rows are replicated, so
    local leaf sums are global — the scan-time unpack's FixHistogram
    subtraction stays valid (dataset.cpp:750-769).
    """
    axis: str
    num_devices: int
    num_features: int                # F_pad: ORIGINAL feature space width
    num_bundles: int                 # G_pad: divisible by num_devices
    bundle_col: object               # [F_pad] i32 bundled column of feature f

    # grower: histograms stay in per-device bundle blocks; the unpack to
    # original feature space happens at scan time with a localized col map
    bundled_blocks = True

    @property
    def block(self) -> int:
        return self.num_bundles // self.num_devices

    def reduce_scalars(self, *xs):
        return xs                     # rows replicated -> sums already global

    def hist_X(self, X):
        i = jax.lax.axis_index(self.axis)
        return jax.lax.dynamic_slice_in_dim(X, i * self.block, self.block,
                                            axis=1)

    def reduce_hist(self, hist):
        return hist                   # [S, G/D, Bb, 3] already global

    reduced_hist_features = SerialComm.reduced_hist_features

    def block_meta(self, feature_ok, num_bins, missing_code, default_bin,
                   is_cat) -> BlockMeta:
        i = jax.lax.axis_index(self.axis)
        owned = jnp.asarray(self.bundle_col) // self.block == i
        return BlockMeta(feature_ok & owned, num_bins, missing_code,
                         default_bin, is_cat, jnp.asarray(0, jnp.int32))

    def localize_bundle(self, bundle):
        """Global bundle tables -> this device's block-local view: the
        [F] column map shifted into the block (clipped; non-owned features
        are masked off by ``block_meta``) and the [G, Bb] code-owner table
        sliced to the owned columns (the native scan is driven by it)."""
        i = jax.lax.axis_index(self.axis)
        return bundle._replace(
            col=jnp.clip(bundle.col - i * self.block, 0, self.block - 1),
            code_feat=jax.lax.dynamic_slice_in_dim(
                bundle.code_feat, i * self.block, self.block, axis=0))

    scan_block = SerialComm.scan_block
    sync_splits = DataParallelComm.sync_splits

    def collective_bytes(self, num_slots: int, num_bins_padded: int,
                         use_categorical: bool = True,
                         hist_bins: int = None) -> dict:
        """Bundled feature-parallel: bundles are the partition unit but the
        wave collective is still only the candidate all-gather (2*num_slots
        slot+sibling scan rows)."""
        return {
            "allgather_splits": (self.num_devices * 2 * num_slots
                                 * _split_candidate_bytes(num_bins_padded,
                                         use_categorical)),
        }


@dataclass(frozen=True)
class DataParallelBundledComm:
    """Data-parallel under the NATIVE EFB scan: rows sharded on ``axis``,
    the per-wave histogram reduce-scatter runs over BUNDLE-COLUMN blocks.

    The whole point of the bundle-space redesign applied to the collective:
    the reference's ReduceScatter of HistogramBinEntry moves post-EFB
    feature-group histograms (its storage unit IS the group), never raw
    features — here the psum_scatter payload shrinks from ``S * F * B``
    to ``S * G * Bb`` entries, and each device scans the member features
    of its own bundle block natively (per_feature_best_bundled with the
    block-localized code tables). Split candidates carry GLOBAL original
    feature indices, so the all-gather argmax (SyncUpGlobalBestSplit) is
    unchanged. The legacy arm (``tpu_efb_unpack=true``) keeps the plain
    :class:`DataParallelComm` with its unpack-before-collective layout.
    """
    axis: str
    num_devices: int
    num_features: int                # F_pad: ORIGINAL feature space width
    num_bundles: int                 # G_pad: divisible by num_devices
    bundle_col: object               # [F_pad] i32 bundled column of feature f

    # grower: hist/cache stay in per-device bundle blocks; the scan runs
    # natively on the block with localized code tables
    bundled_blocks = True

    @property
    def block(self) -> int:
        return self.num_bundles // self.num_devices

    def reduce_scalars(self, *xs):
        return tuple(jax.lax.psum(x, self.axis) for x in xs)

    def hist_X(self, X):
        return X                      # all bundled columns, local rows

    def reduce_hist(self, hist):
        # [S, G, Bb, 3] local sums -> [S, G/D, Bb, 3] global sums of my
        # bundle block (the F*B -> G*Bb collective shrink)
        S, G, B, C = hist.shape
        D = self.num_devices
        blocks = hist.reshape(S, D, self.block, B, C)
        blocks = jnp.moveaxis(blocks, 1, 0)           # [D, S, G/D, B, C]
        return jax.lax.psum_scatter(blocks, self.axis, scatter_dimension=0,
                                    tiled=False)

    def reduced_hist_features(self, F_hist: int) -> int:
        return self.block

    def block_meta(self, feature_ok, num_bins, missing_code, default_bin,
                   is_cat) -> BlockMeta:
        # full-width ORIGINAL-feature metadata, masked to the member
        # features of this device's bundle block (candidates stay global)
        i = jax.lax.axis_index(self.axis)
        owned = jnp.asarray(self.bundle_col) // self.block == i
        return BlockMeta(feature_ok & owned, num_bins, missing_code,
                         default_bin, is_cat, jnp.asarray(0, jnp.int32))

    localize_bundle = FeatureParallelBundledComm.localize_bundle

    scan_block = SerialComm.scan_block
    sync_splits = DataParallelComm.sync_splits

    def collective_bytes(self, num_slots: int, num_bins_padded: int,
                         use_categorical: bool = True,
                         hist_bins: int = None) -> dict:
        """Like DataParallelComm but the histogram reduce-scatter is
        BUNDLE-space wide: ``num_bundles * hist_bins`` columns instead of
        ``num_features * num_bins_padded`` — the analytic half of the
        collective shrink, validated against the compiled HLO
        (tests/test_multichip_parity.py)."""
        scan_slots = 2 * num_slots
        return {
            "psum_root_scalars": 3 * 4,
            "psum_scatter_hist": (num_slots * self.num_bundles
                                  * (hist_bins or num_bins_padded) * 3 * 4),
            "allgather_splits": (self.num_devices * scan_slots
                                 * _split_candidate_bytes(num_bins_padded,
                                         use_categorical)),
        }


@dataclass(frozen=True)
class VotingParallelComm:
    """Rows sharded; PV-Tree two-phase split finding with top-k voting."""
    axis: str
    num_devices: int
    num_features: int
    top_k: int                        # config top_k (voting_parallel_tree_learner)

    def reduce_scalars(self, *xs):
        return tuple(jax.lax.psum(x, self.axis) for x in xs)

    def hist_X(self, X):
        return X

    def reduce_hist(self, hist):
        return hist                   # kept LOCAL; reduction happens per-vote

    reduced_hist_features = SerialComm.reduced_hist_features

    def block_meta(self, feature_ok, num_bins, missing_code, default_bin,
                   is_cat) -> BlockMeta:
        return BlockMeta(feature_ok, num_bins, missing_code, default_bin,
                         is_cat, jnp.asarray(0, jnp.int32))

    def scan_block(self, hist, pg, ph, pc, bm: BlockMeta, spec,
                   bundle=None) -> LocalGains:
        import dataclasses

        # Phase 1 — local proposals from LOCAL leaf sums (the histogram here
        # is this device's un-reduced partial, so its bin sums ARE the local
        # leaf sums) with min_data/min_hessian constraints divided by the
        # device count — mirroring the reference's local_tree_config_
        # (voting_parallel_tree_learner.cpp:54-56) and smaller_leaf_splits_
        # initialized from the local partition (:286-293).
        local_pg = jnp.sum(hist[:, 0, :, 0], axis=-1)             # [S]
        local_ph = jnp.sum(hist[:, 0, :, 1], axis=-1)
        local_pc = jnp.sum(hist[:, 0, :, 2], axis=-1)
        local_spec = dataclasses.replace(
            spec,
            min_data_in_leaf=spec.min_data_in_leaf / self.num_devices,
            min_sum_hessian_in_leaf=(spec.min_sum_hessian_in_leaf
                                     / self.num_devices))
        pf_local, _ = block_per_feature(hist, local_pg, local_ph, local_pc,
                                        bm, local_spec, bundle)
        return LocalGains(pf_local.gain)

    def sync_splits(self, local: LocalGains, pick_cols, pg, ph, pc,
                    bm: BlockMeta, spec, bundle=None) -> SplitCandidates:
        local_gain = local.gain
        S = local_gain.shape[0]
        F = self.num_features
        k = max(1, min(self.top_k, F))
        k2 = min(2 * k, F)

        top_gain, top_feat = jax.lax.top_k(local_gain, k)           # [S, k]
        votes = jnp.zeros((S, F), jnp.float32).at[
            jnp.arange(S)[:, None], top_feat].add(
                jnp.where(jnp.isfinite(top_gain), 1.0, 0.0))
        votes = jax.lax.psum(votes, self.axis)                      # GlobalVoting :165

        # Phase 2 — reduce only the winning features' histograms. Exact
        # lexicographic (votes, summed local gain) order: each feature's gain
        # is replaced by its ordinal rank within the slot (an integer < F),
        # so votes*F + rank is exact integer arithmetic at ANY gain magnitude
        # — the reference breaks ties via MaxK over weighted gains
        # (voting_parallel_tree_learner.cpp:165-196); a sigmoid tie-break
        # saturates for >1e2-scale gains and resolves arbitrarily.
        finite_gain = jnp.where(jnp.isfinite(local_gain), local_gain, 0.0)
        sum_gain = jax.lax.psum(finite_gain, self.axis)             # [S, F]
        order = jnp.argsort(sum_gain, axis=1)                       # ascending
        gain_rank = jnp.zeros((S, F), jnp.int32).at[
            jnp.arange(S)[:, None], order].set(
                jnp.arange(F, dtype=jnp.int32)[None, :])
        rank_score = votes.astype(jnp.int32) * F + gain_rank
        _, sel = jax.lax.top_k(rank_score, k2)                      # [S, k2] global ids
        if bundle is not None:
            # native EFB: reduce only the winning features' BUNDLE columns
            # — the psum payload is [S, k2, Bb, 3] instead of feature-space
            # [S, k2, B, 3] — and scan each selected member natively on its
            # gathered column (a per-slot one-member bundle view; the
            # default-bin hole at off+db stays unowned so the FixHistogram
            # deficit reconstructs it exactly like the global scan)
            sel_col = jnp.asarray(bundle.col)[sel]                  # [S, k2]
            sel_hist = pick_cols(sel_col)                           # [S,k2,Bb,3]
            Bb = sel_hist.shape[2]
            sel_hist = jax.lax.psum(sel_hist, self.axis)
            iota_c = jnp.arange(Bb, dtype=jnp.int32)
            jidx = jnp.arange(k2, dtype=jnp.int32)

            def scan_slot_b(h_slot, lo_, hi_, off_, nb_, mc_, db_, ok_,
                            pg_, ph_, pc_):
                owned = ((iota_c[None, :] >= lo_[:, None])
                         & (iota_c[None, :] < hi_[:, None])
                         & (iota_c[None, :] != (off_ + db_)[:, None]))
                cf = jnp.where(owned, jidx[:, None], -1)
                pf = per_feature_best_bundled(
                    h_slot[None], pg_[None], ph_[None], pc_[None],
                    nb_, mc_, db_, ok_, jidx, lo_, hi_, off_, cf,
                    **spec.hyperparams())
                cand = reduce_features(pf,
                                       num_bins_padded=spec.num_bins_padded)
                return jax.tree.map(lambda a: a[0], cand)

            cand = jax.vmap(scan_slot_b)(
                sel_hist, bundle.lo[sel], bundle.hi[sel], bundle.off[sel],
                bm.num_bins[sel], bm.missing_code[sel], bm.default_bin[sel],
                bm.feature_ok[sel] & ~bm.is_cat[sel], pg, ph, pc)
        else:
            sel_hist = pick_cols(sel)                               # [S, k2, B, 3]
            sel_hist = jax.lax.psum(sel_hist, self.axis)

            # Per-slot feature metadata: vmap the scan over slots since
            # each slot selected different features.
            def scan_slot(h_slot, sel_slot, pg_, ph_, pc_):
                bm_slot = BlockMeta(
                    bm.feature_ok[sel_slot], bm.num_bins[sel_slot],
                    bm.missing_code[sel_slot], bm.default_bin[sel_slot],
                    bm.is_cat[sel_slot], jnp.asarray(0, jnp.int32))
                cand = find_block_splits(h_slot[None], pg_[None], ph_[None],
                                         pc_[None], bm_slot, spec)
                return jax.tree.map(lambda a: a[0], cand)

            cand = jax.vmap(scan_slot)(sel_hist, sel, pg, ph, pc)
        # map local candidate index -> global feature id
        feat = jnp.take_along_axis(sel, cand.feature[:, None], axis=1)[:, 0]
        return cand._replace(feature=feat.astype(jnp.int32))

    def collective_bytes(self, num_slots: int, num_bins_padded: int,
                         use_categorical: bool = True,
                         hist_bins: int = None) -> dict:
        """PV-Tree's O(k/F) trade made explicit: votes + gain ranks are
        [S, F] f32 psums, and only the ~2k winning features' histogram
        columns reduce (CopyLocalHistogram,
        voting_parallel_tree_learner.cpp:197) — compare psum_selected_hist
        here against DataParallelComm's full psum_scatter_hist. Every one
        of these runs inside ``sync_splits``, whose slot axis is the
        2*num_slots slot+sibling scan (grower.py step 4). Under the native
        EFB arm the selected columns are BUNDLE columns, so their psum is
        ``hist_bins`` (Bb) wide — the bundled-run fix for an estimate that
        used to charge feature-space widths regardless."""
        F = self.num_features
        k2 = min(2 * max(1, min(self.top_k, F)), F)
        scan_slots = 2 * num_slots
        return {
            "psum_root_scalars": 3 * 4,
            "psum_votes": scan_slots * F * 4,
            "psum_gain_ranks": scan_slots * F * 4,
            "psum_selected_hist": (scan_slots * k2
                                   * (hist_bins or num_bins_padded) * 3 * 4),
            "allgather_splits": (self.num_devices * scan_slots
                                 * _split_candidate_bytes(num_bins_padded,
                                         use_categorical)),
        }


def choose_tree_learner(num_data: int, num_features: int, n_devices: int,
                        top_k: int = 20, mesh_axis: str = "auto") -> str:
    """Resolve ``tree_learner=auto`` from the shape class — the reference's
    Parallel-Learning-Guide table (docs/Parallel-Learning-Guide.rst there,
    docs/Parallel-Learning-Guide.md here): few rows + many features ->
    feature-parallel; many rows -> data-parallel (the common case); many
    rows AND many features -> voting-parallel, but only when PV-Tree's
    O(k/F) trade actually shrinks the wave collective (F >> top_k).

    ``mesh_axis`` is the override knob (config ``tpu_mesh_axis``):
    ``rows`` constrains the choice to the row-sharded strategies
    (data/voting), ``features`` forces feature-parallel, ``auto`` lets the
    shape class decide. Explicitly setting ``tree_learner`` bypasses this
    function entirely.
    """
    if n_devices <= 1:
        return "serial"
    # shape-class thresholds: "large" rows means the per-device histogram
    # pass dominates setup (row sharding pays off); "large" features means
    # the full-width histogram collective is the wave bottleneck
    large_data = num_data >= 1_000_000
    large_feature = num_features >= 256
    if mesh_axis == "features":
        return "feature"
    if large_data and large_feature and num_features >= 8 * max(top_k, 1):
        return "voting"
    if not large_data and large_feature and mesh_axis != "rows":
        return "feature"
    return "data"


class ParallelContext:
    """Mesh + strategy + shardings for one Booster.

    ``strategy`` follows the reference's `tree_learner` values
    (config.h TreeLearnerType): serial | feature | data | voting. The 1-D
    mesh axis is NAMED by the role the strategy gives it — ``rows`` for the
    row-sharded strategies (data/voting), ``features`` for feature-parallel
    (where ``hist_X`` block-slices columns by axis index) — so shardings,
    telemetry, and HLO dumps all say which dataset dimension the mesh
    splits.
    """

    def __init__(self, strategy: str, devices, top_k: int = 20):
        self.strategy = strategy
        self.devices = list(devices)
        self.num_devices = len(self.devices)
        self.top_k = top_k
        if strategy == "serial" or self.num_devices == 1:
            self.strategy = "serial"
            self.mesh = None
        else:
            self.mesh = Mesh(np.array(self.devices), (self.axis_kind,))

    @property
    def axis_kind(self) -> str:
        """Which dataset dimension the mesh axis shards: ``rows`` (data/
        voting), ``features`` (feature-parallel), ``none`` (serial)."""
        if self.strategy in ("data", "voting"):
            return "rows"
        if self.strategy == "feature":
            return "features"
        return "none"

    @property
    def ROW_AXIS(self) -> str:
        """The mesh axis name comm objects close over (role-named; kept as
        the historical attribute the shard_map specs were written against)."""
        return self.axis_kind if self.mesh is not None else "rows"

    def describe(self) -> dict:
        """Host-side mesh facts for telemetry / bench JSON."""
        return {"strategy": self.strategy,
                "n_devices": self.num_devices,
                "mesh_axis": self.axis_kind,
                "multi_process": bool(self.multi_process),
                "platform": self.devices[0].platform if self.devices else None}

    def residency_key(self) -> tuple:
        """Hashable fingerprint of everything that determines a device
        array's placement under this context — the Dataset-level residency
        cache (dataset.py ``device_put_cached``) keys on it so a booster
        built over a different mesh/strategy never reuses a stale layout."""
        return (self.strategy, self.axis_kind, self.num_devices,
                tuple(str(d) for d in self.devices))

    @property
    def multi_process(self) -> bool:
        """True under jax.distributed multi-host execution."""
        return self.mesh is not None and jax.process_count() > 1

    # -------------------------------------------------------------- shapes

    def pad_features_to(self, F: int) -> int:
        """Feature-block strategies need F divisible by the device count."""
        if self.strategy in ("data", "feature") and self.num_devices > 1:
            D = self.num_devices
            return ((F + D - 1) // D) * D
        return F

    def pad_rows_multiple(self) -> int:
        """Row padding granularity (rows sharded -> multiple of D)."""
        return self.num_devices if self.strategy in ("data", "voting") else 1

    # ---------------------------------------------------------------- comm

    def make_comm(self, num_features: int, num_bundles: int = 0,
                  bundle_col=None):
        """``num_bundles > 0`` selects the bundle-partitioned comm for the
        block strategies: always for feature-parallel (bundles ARE the
        partition unit there, both EFB arms), and for data-parallel only on
        the native bundle-space arm (the legacy unpack arm reduces
        feature-space histograms through the plain DataParallelComm).
        Voting needs no bundled twin — its two halves branch on the
        per-call ``bundle`` tables."""
        if self.strategy == "data":
            if num_bundles:
                return DataParallelBundledComm(
                    self.ROW_AXIS, self.num_devices, num_features,
                    num_bundles, bundle_col)
            return DataParallelComm(self.ROW_AXIS, self.num_devices, num_features)
        if self.strategy == "feature":
            if num_bundles:
                return FeatureParallelBundledComm(
                    self.ROW_AXIS, self.num_devices, num_features,
                    num_bundles, bundle_col)
            return FeatureParallelComm(self.ROW_AXIS, self.num_devices, num_features)
        if self.strategy == "voting":
            return VotingParallelComm(self.ROW_AXIS, self.num_devices,
                                      num_features, self.top_k)
        return SerialComm(num_features)

    # ---------------------------------------------------------- shard_map

    def row_sharding(self):
        """NamedSharding for [N, ...] arrays whose rows are distributed."""
        if self.mesh is None or self.strategy == "feature":
            return None
        return NamedSharding(self.mesh, P(self.ROW_AXIS))

    def sharding(self, kind: str = "repl"):
        """NamedSharding for this context's resident training arrays, or
        None on a single device (plain device_put). Kinds: ``rows`` ([N]
        sharded), ``rows0`` ([N, F], rows on dim 0), ``rows1`` ([K, N],
        rows on dim 1), ``repl`` (replicated). Row sharding only applies to
        the row-sharded strategies; feature-parallel replicates rows like
        the reference's FeatureParallel learner (every machine holds all
        data, feature_parallel_tree_learner.cpp) and slices columns at
        trace time instead."""
        if self.mesh is None:
            return None
        if kind == "repl" or self.strategy == "feature":
            spec = P()
        else:
            spec = {"rows": P(self.ROW_AXIS), "rows0": P(self.ROW_AXIS, None),
                    "rows1": P(None, self.ROW_AXIS)}[kind]
        return NamedSharding(self.mesh, spec)

    def shard_grow(self, grow_fn: Callable) -> Callable:
        """Wrap ``grow_fn(X, grad, hess, included, feature_ok, num_bins,
        missing_code, default_bin)`` in shard_map with this strategy's specs.
        Tree outputs are replicated; leaf_id follows the row sharding; the
        wave loop's counters (grower.WaveStats, leading axis 1 per device)
        concatenate over the mesh axis, one row per device: each device
        counts its own shard, and no collective is spent on a counter."""
        if self.mesh is None:
            return grow_fn
        rows = P(self.ROW_AXIS) if self.strategy in ("data", "voting") else P()
        rows2d = P(self.ROW_AXIS, None) if self.strategy in ("data", "voting") else P()
        in_specs = (rows2d, rows, rows, rows, P(), P(), P(), P(), P())
        # (TreeArrays..., leaf_id, WaveStats...)
        out_specs = (P(), rows, P(self.ROW_AXIS))
        return jax.shard_map(grow_fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)


def parse_machine_list(config) -> list:
    """Machine list as ``[(host, port), ...]`` from ``machines`` (comma- or
    newline-separated ``host:port`` / ``host port``) or ``machine_list_file``
    (reference: NetworkConfig, config.h:264-272; file format of
    examples/parallel_learning/mlist.txt).

    Each entry is validated individually: a malformed line (bare host, junk
    port, empty host) raises a ValueError naming the offending entry and the
    expected format instead of an opaque unpack/int() traceback."""
    text = config.machines or ""
    if not text and config.machine_list_file:
        with open(config.machine_list_file) as fh:
            text = fh.read()
    out = []
    for chunk in text.replace(",", "\n").splitlines():
        chunk = chunk.strip()
        if not chunk:
            continue
        if ":" in chunk:
            host, _, port_s = chunk.partition(":")
        else:
            parts = chunk.split()
            host, port_s = (parts[0], parts[1]) if len(parts) == 2 else \
                (chunk, "")
        host, port_s = host.strip(), port_s.strip()
        try:
            port = int(port_s)
        except ValueError:
            port = -1
        if not host or ":" in port_s or not (0 < port < 65536):
            raise ValueError(
                f"malformed machine list entry {chunk!r}: expected "
                f"'host:port' or 'host port' with port in 1..65535 "
                f"(e.g. '10.0.0.1:12400')")
        out.append((host, port))
    return out


def _local_rank(machines, local_listen_port: int) -> int:
    """This process's rank: the machine-list entry whose host is a local
    address AND whose port matches local_listen_port (the reference's rank
    discovery, linkers_socket.cpp:20-47, disambiguated by listen port so
    multiple ranks can share a host)."""
    import socket
    local_names = {"localhost", "127.0.0.1", socket.gethostname()}
    try:
        local_names.update(socket.gethostbyname_ex(socket.gethostname())[2])
    except OSError:
        pass
    matches = [i for i, (h, p) in enumerate(machines)
               if p == local_listen_port and (h in local_names)]
    if len(matches) == 1:
        return matches[0]
    # fall back: unique local host regardless of port
    host_matches = [i for i, (h, _) in enumerate(machines) if h in local_names]
    if len(host_matches) == 1:
        return host_matches[0]
    raise RuntimeError(
        f"cannot determine machine rank: {len(matches)} machine-list entries "
        f"match local addresses {sorted(local_names)} with port "
        f"{local_listen_port}")


class _PerThreadSeq:
    """The host_allgather sequence counter, kept PER-THREAD. A real gang has
    one process per rank, so plain module state advances in SPMD lockstep;
    the in-process gang simulations (robustness/chaos.py, bench --chaos-dist:
    one thread per simulated rank over a FakeKVStore) need the same
    per-rank isolation or concurrent ranks steal each other's sequence
    numbers and the exchange keys never meet. Indexable like the plain list
    it replaced (tests read ``_host_allgather_seq[0]``)."""

    def __init__(self):
        import threading
        self._local = threading.local()

    def _lst(self):
        lst = getattr(self._local, "lst", None)
        if lst is None:
            lst = self._local.lst = [0]
        return lst

    def __getitem__(self, i):
        return self._lst()[i]

    def __setitem__(self, i, value):
        self._lst()[i] = value


_host_allgather_seq = _PerThreadSeq()

# chaos-injection hook (robustness/chaos.py): when set, every KV client
# host_allgather obtains is wrapped before use — fault paths become
# exercisable on a real cluster without touching call sites
_client_wrapper = None


def host_allgather(obj, tag: str, timeout_ms: int = 600_000, *,
                   client=None, rank: int = None, world: int = None) -> list:
    """Gather one picklable object per process, returned rank-ordered.

    Host-side analog of the reference's Network::Allgather for setup-time
    payloads (serialized BinMappers, dataset_loader.cpp:889; row counts for
    pre-partitioned data, dataset_loader.cpp:159-221) — exchanged through
    jax's coordination-service KV store, not a hand-built TCP mesh. The call
    sequence must be identical on every process (SPMD), which makes the
    per-tag sequence number agree.

    Resilience (docs/Fault-Tolerance.md): the KV set and each per-rank
    get+unpickle are retried with exponential backoff + jitter
    (``LGBM_TPU_COMM_*`` env knobs) — a transient coordination-service
    hiccup or a corrupted payload re-fetches instead of killing the run —
    and exhausted retries raise a ``CommTimeoutError`` naming the tag,
    sequence number, and both ranks. Cleanup failures are *logged*, never
    swallowed, and this rank's key is deleted only when the done-barrier
    actually succeeded (deleting earlier races peers still reading).

    ``client``/``rank``/``world`` are injectable for tests and the chaos
    harness (robustness/chaos.py FakeKVStore / ChaosKVClient); they default
    to the live jax.distributed state.
    """
    import pickle
    import time as _time

    from ..robustness.retry import (PeerLostError, comm_attempts, retry_call)
    from ..utils.log import Log

    if client is None:
        client = distributed_client()
        if client is None or jax.process_count() <= 1:
            return [obj]
    if _client_wrapper is not None:
        client = _client_wrapper(client)
    rank = jax.process_index() if rank is None else rank
    world = jax.process_count() if world is None else world
    if world <= 1:
        return [obj]
    from .. import observability as _obs
    seq = _host_allgather_seq[0]
    _host_allgather_seq[0] += 1
    key = f"lgbm_hostgather/{tag}/{seq}"
    payload = pickle.dumps(obj)
    _obs.inc("comm.host_allgather")
    # the whole exchange is one host-side "comm" span (set + per-peer gets
    # + cleanup barrier): a pure host boundary, no device arrays touched
    with _obs.span("comm", op="host_allgather", tag=tag, seq=seq,
                   rank=rank, world=world):
        # allow_overwrite makes the retried set idempotent: a first attempt
        # that landed server-side but lost its ack re-writes the identical
        # payload instead of failing every retry with ALREADY_EXISTS
        retry_call(lambda: client.key_value_set_bytes(f"{key}/{rank}",
                                                      payload,
                                                      allow_overwrite=True),
                   what=f"host_allgather set tag={tag!r} seq={seq} "
                        f"rank={rank}")
        out = []
        # the timeout is a TOTAL budget per peer, split across retry
        # attempts — a dead peer costs ~timeout_ms, not
        # attempts x timeout_ms (retrying only pays off for the
        # transient-error/corrupt-payload cases anyway)
        per_attempt_ms = max(1, timeout_ms // comm_attempts())
        slowest_rank, slowest_wait = rank, -1.0
        for r in range(world):
            if r == rank:
                out.append(obj)
                continue

            def _get(r=r):
                # get + unpickle as ONE retried unit: a transiently
                # corrupted payload (bit rot in flight) re-fetches cleanly
                raw = client.blocking_key_value_get_bytes(f"{key}/{r}",
                                                          per_attempt_ms)
                return pickle.loads(raw)

            t0 = _time.monotonic()
            try:
                out.append(retry_call(
                    _get, what=f"host_allgather get tag={tag!r} seq={seq} "
                               f"rank={rank}<-{r}"))
            except Exception as e:
                # the per-wave deadline expired on THIS peer: attribute the
                # loss to the rank, not a generic hang — fleet restart
                # policy keys off the typed error and the metrics
                _obs.inc("comm.timeouts")
                _obs.inc("fault.peer_lost")
                _obs.get_registry().gauge("comm.slowest_rank").set(r)
                raise PeerLostError(
                    f"host_allgather tag={tag!r} seq={seq}: rank {rank} "
                    f"could not fetch rank {r}'s shard within "
                    f"~{timeout_ms} ms total — peer rank {r} is the "
                    f"missing/slowest rank in this wave "
                    f"({e.__class__.__name__}: {e})", rank=r) from e
            waited = _time.monotonic() - t0
            if waited > slowest_wait:
                slowest_rank, slowest_wait = r, waited
        if world > 1 and slowest_wait >= 0.0:
            _obs.get_registry().gauge("comm.slowest_rank").set(slowest_rank)
        # every rank must have READ every shard before any key disappears
        barrier_ok = False
        try:
            client.wait_at_barrier(f"{key}/done", timeout_ms)
            barrier_ok = True
        except Exception as e:                               # noqa: BLE001
            _obs.inc("comm.barrier_failures")
            Log.warning("host_allgather tag=%r seq=%d rank=%d: cleanup "
                        "barrier failed (%s: %s); leaving key %s/%d for the "
                        "coordination service to expire", tag, seq, rank,
                        type(e).__name__, e, key, rank)
        if barrier_ok:
            try:
                client.key_value_delete(f"{key}/{rank}")
            except Exception as e:                           # noqa: BLE001
                Log.warning("host_allgather tag=%r seq=%d rank=%d: key "
                            "delete failed (%s: %s)", tag, seq, rank,
                            type(e).__name__, e)
        return out


def distributed_client():
    """The jax coordination-service client, or None when not running under
    jax.distributed (single probe point for the private-API access)."""
    from jax._src import distributed as _dist
    return _dist.global_state.client


def init_distributed(config) -> bool:
    """Wire multi-host execution when the reference's network params are set
    (reference: Network::Init + rank discovery, application.cpp:167-178,
    linkers_socket.cpp:20-47 — here the transport is jax.distributed's
    coordination service + XLA collectives over ICI/DCN instead of a TCP
    mesh). Returns True if running multi-process after the call."""
    import jax
    if distributed_client() is not None:
        return jax.process_count() > 1        # already initialized
    if getattr(config, "num_machines", 1) <= 1:
        return False
    machines = parse_machine_list(config)
    if len(machines) <= 1:
        return False
    if len(machines) != config.num_machines:
        from ..utils.log import Log
        Log.warning("num_machines=%d but machine list has %d entries; "
                    "using the list", config.num_machines, len(machines))
    rank = _local_rank(machines, config.local_listen_port)
    coord = f"{machines[0][0]}:{machines[0][1]}"
    from ..robustness.retry import CommTimeoutError, retry_call

    def _reset_partial_init():
        # a failed connect() leaves jax's global_state.client (and rank 0's
        # service) assigned, so a bare re-call of initialize() raises
        # 'should only be called once' instead of retrying the handshake —
        # tear the partial state down between attempts
        try:
            jax.distributed.shutdown()
        except Exception as e:                               # noqa: BLE001
            from ..utils.log import Log
            Log.debug("init_distributed: shutdown after failed attempt "
                      "itself failed (%s: %s); clearing state directly",
                      type(e).__name__, e)
            try:
                from jax._src import distributed as _dist
                _dist.global_state.client = None
                _dist.global_state.service = None
                _dist.global_state.preemption_sync_manager = None
            except Exception:                                # noqa: BLE001
                pass

    def _initialize():
        try:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=len(machines),
                                       process_id=rank,
                                       # reference time_out is MINUTES
                                       # (config.h:272)
                                       initialization_timeout=config.time_out
                                       * 60)
        except Exception:
            _reset_partial_init()
            raise

    # pod-startup churn routinely loses the first coordination-service
    # handshake (the coordinator container comes up seconds after the
    # workers) — retry with backoff instead of dying on attempt one
    from .. import observability as _obs
    try:
        with _obs.span("comm", op="init_distributed", coordinator=coord,
                       rank=rank, world=len(machines)):
            retry_call(_initialize,
                       what=f"jax.distributed.initialize coordinator={coord} "
                            f"rank={rank}/{len(machines)}")
    except Exception as e:
        _obs.inc("comm.timeouts")
        raise CommTimeoutError(
            f"init_distributed: rank {rank} could not join the "
            f"coordination service at {coord} "
            f"(world size {len(machines)}, timeout {config.time_out} min): "
            f"{type(e).__name__}: {e}") from e
    # the CPU backend runs multiprocess computations only through its gloo
    # collectives; without this a 2-process CPU gang dies in the FIRST
    # fused step with "Multiprocess computations aren't implemented on the
    # CPU backend". Selected only once the handshake landed a live
    # distributed client (gloo's TCP store rides it; selecting gloo with
    # no client poisons every later backend init) and before the
    # process_count() below instantiates the backend — Network::Init
    # ordering (init_distributed before any device work) matters here too.
    # TPU/GPU read their collectives from the platform.
    if "cpu" in (os.environ.get("JAX_PLATFORMS") or "").lower():
        from jax._src import distributed as _dist
        if _dist.global_state.client is not None:
            try:
                jax.config.update("jax_cpu_collectives_implementation",
                                  "gloo")
            except Exception as e:                           # noqa: BLE001
                from ..utils.log import Log
                Log.warning("could not select gloo CPU collectives "
                            "(%s: %s) — multiprocess CPU computations may "
                            "be unavailable", type(e).__name__, e)
    return jax.process_count() > 1


def select_devices(config):
    """Devices for this booster, honoring the reference's ``device`` param:
    ``tpu`` (default) uses the accelerator backend; ``cpu`` forces the host
    CPU backend — which under `--xla_force_host_platform_device_count=N`
    exposes N virtual devices, the test bed for every parallel strategy.

    A run that asked for the accelerator never finishes on the CPU by
    accident: if jax found no accelerator and nobody pinned the CPU
    (``JAX_PLATFORMS=cpu``, which the test harness and the CPU-only bench
    modes set), this fails instead of training on the host."""
    want = getattr(config, "device", "tpu")
    if want == "cpu":
        return jax.devices("cpu")
    devices = jax.devices()
    if (devices[0].platform == "cpu"
            and "cpu" not in (jax.config.jax_platforms or "")):
        from ..utils.log import Log
        Log.fatal("device=%s requested but jax found no accelerator "
                  "(default backend: cpu). Set device=cpu or "
                  "JAX_PLATFORMS=cpu to train on the host CPU on purpose.",
                  want)
    return devices


def make_parallel_context(config, devices=None, shape=None) -> ParallelContext:
    """Build the context from config (reference: Network::Init,
    application.cpp:167-178 — here the 'network' is the device mesh, and a
    machine list triggers jax.distributed multi-host wiring).

    ``shape`` is an optional ``(num_data, num_features)`` hint that
    ``tree_learner=auto`` resolves against (``choose_tree_learner``); the
    booster passes its training matrix shape. Without a hint, auto falls
    back to the reference's distributed default (data parallel)."""
    strategy = getattr(config, "tree_learner", "serial")
    top_k = getattr(config, "top_k", 20)
    if devices is None:
        multi = init_distributed(config)
        devices = select_devices(config)
        nm = getattr(config, "num_machines", 1)
        if multi:
            # global mesh over every host's chips; serial would device_put to
            # another process's chip — pick the reference's distributed
            # default (data parallel) instead
            if strategy == "serial":
                from ..utils.log import Log
                Log.warning("tree_learner=serial is not distributed; using "
                            "tree_learner=data across %d processes",
                            jax.process_count())
                strategy = "data"
        elif nm and nm > 1:
            # single-process fallback: emulate machines with local devices
            devices = devices[: min(nm, len(devices))]
        elif strategy == "serial":
            devices = devices[:1]
    if strategy == "auto":
        from ..utils.log import Log
        if shape is None:
            strategy = "data" if len(devices) > 1 else "serial"
            Log.warning("tree_learner=auto without a dataset shape hint; "
                        "using tree_learner=%s", strategy)
        else:
            strategy = choose_tree_learner(
                int(shape[0]), int(shape[1]), len(devices), top_k=top_k,
                mesh_axis=getattr(config, "tpu_mesh_axis", "auto"))
            Log.info("tree_learner=auto resolved to %s (%d rows x %d "
                     "features over %d device(s), tpu_mesh_axis=%s)",
                     strategy, shape[0], shape[1], len(devices),
                     getattr(config, "tpu_mesh_axis", "auto"))
        if strategy == "serial" and len(devices) > 1:
            devices = devices[:1]
    return ParallelContext(strategy, devices, top_k=top_k)
