"""Vectorized best-split search over histograms.

TPU re-formulation of FeatureHistogram::FindBestThreshold
(reference: src/treelearner/feature_histogram.hpp:72-101,314-455): the
reference's two sequential scans per feature (dir=+1 / dir=-1 with
missing-value default-direction learning) become masked cumulative sums over
the bin axis, evaluated for all (slot, feature, threshold, direction)
candidates at once, followed by one argmax.

The search is split into two stages so the distributed tree learners
(parallel/comm.py) can compose them the way the reference composes
FindBestSplitsFromHistograms with its network reductions:

1. ``per_feature_best_numerical`` — best threshold *per feature*
   (the reference's per-feature OMP loop, serial_tree_learner.cpp:451-516),
2. ``reduce_features`` — argmax over the feature axis
   (the reference's ``best_split_per_leaf_`` update); feature-parallel
   learners instead all-gather per-device winners and argmax across devices
   (SyncUpGlobalBestSplit, parallel_tree_learner.h:184-207), voting learners
   use the per-feature gains for PV-Tree vote collection.

Semantics preserved:
- gain = GetLeafSplitGain(l) + GetLeafSplitGain(r) with L1 thresholding
  (feature_histogram.hpp:290-296), candidate valid iff
  gain > parent_gain + min_gain_to_split (:101,362),
- MissingType::NaN — the NaN bin (last) is excluded from the accumulating
  side, so missing rows follow the scan direction's remainder: dir=-1 sends
  them left (default_left=true), dir=+1 right (:349-357,375-386),
- MissingType::Zero — the zero bin is excluded likewise and its threshold
  skipped (skip_default_bin, :338,399),
- features with num_bin<=2 or MissingType::None scan only dir=-1
  (:86-99), with the 2-bin NaN default-direction fix (:96-98),
- min_data_in_leaf / min_sum_hessian_in_leaf constraints on both children.

Categorical features are handled by ops/categorical.py (one-hot and
sorted-prefix modes, feature_histogram.hpp:104-259), which produces the same
``PerFeatureBest`` shape and is merged before ``reduce_features``.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -jnp.inf


class SplitCandidates(NamedTuple):
    """Best split per histogram slot (device arrays, all [S] unless noted).

    Slot-order invariant: slots are assigned to pending leaves in ASCENDING
    leaf-id order (grower.py wave step 1, a cumsum over `needs_hist`), and
    three consumers rely on that one ordering staying consistent — the
    grower's `leaf_of_slot` scatter/gather pair, the compacted histogram
    pass's position->slot derivation (`slot_from_position` over the running
    sum of the per-slot row counts of `_rows_by_slot`'s order), and the scan
    here, whose outputs are written back through `scan_leaves = leaf_of_slot
    ++ siblings`. The scan itself is row-order-independent (it reads
    finished histograms) — but a re-order of slot assignment would silently
    mis-route all three.
    """
    gain: jnp.ndarray          # f32, improvement over parent (-inf if none)
    feature: jnp.ndarray       # i32 inner feature index (GLOBAL)
    threshold: jnp.ndarray     # i32 bin threshold (left: bin <= threshold)
    default_left: jnp.ndarray  # bool
    left_g: jnp.ndarray        # f32 sum of gradients in left child
    left_h: jnp.ndarray        # f32
    left_c: jnp.ndarray        # f32 row count in left child
    is_cat: jnp.ndarray        # bool: categorical split
    cat_mask: jnp.ndarray      # bool [S, B]: left-set over bins (cat splits)


class PerFeatureBest(NamedTuple):
    """Best split per (slot, feature); all arrays [S, F]."""
    gain: jnp.ndarray          # f32, improvement over parent (-inf if none)
    threshold: jnp.ndarray     # i32
    default_left: jnp.ndarray  # bool
    left_g: jnp.ndarray
    left_h: jnp.ndarray
    left_c: jnp.ndarray


def leaf_split_gain(sum_g, sum_h, l1: float, l2: float):
    """(|g|-l1)_+^2 / (h+l2) — feature_histogram.hpp:290-296."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return reg * reg / (sum_h + l2)


def leaf_output(sum_g, sum_h, l1: float, l2: float):
    """-sign(g)(|g|-l1)_+ / (h+l2) — feature_histogram.hpp:304-310.

    A zero denominator (legal under min_sum_hessian_in_leaf=0, lambda_l2=0
    with vanishing hessians) yields 0, not Inf: the score update resolves
    leaf values through table_lookup's one-hot contraction, which touches
    every table row, so a single Inf/NaN leaf would poison all rows.
    """
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    denom = sum_h + l2
    out = -jnp.sign(sum_g) * reg / denom
    return jnp.where((denom > 0) & jnp.isfinite(out), out, 0.0)


def per_feature_best_numerical(
    hist: jnp.ndarray,        # [S, F, B, 3] (sum_g, sum_h, count)
    parent_g: jnp.ndarray,    # [S]
    parent_h: jnp.ndarray,    # [S]
    parent_c: jnp.ndarray,    # [S]
    num_bins: jnp.ndarray,    # [F] i32
    missing_code: jnp.ndarray,  # [F] i32: 0=none, 1=zero, 2=nan
    default_bin: jnp.ndarray,   # [F] i32
    feature_ok: jnp.ndarray,    # [F] bool (numerical & feature_fraction mask)
    *,
    lambda_l1: float,
    lambda_l2: float,
    min_data_in_leaf: float,
    min_sum_hessian_in_leaf: float,
    min_gain_to_split: float,
) -> PerFeatureBest:
    """Best numerical threshold for every (slot, feature) pair.

    Gains are already shifted by the parent gain + min_gain_to_split
    (feature_histogram.hpp:101), so a finite value means "valid improvement".
    """
    S, F, B, _ = hist.shape
    g = hist[..., 0]
    h = hist[..., 1]
    c = hist[..., 2]
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]                 # [1, B]
    nb = num_bins[:, None]                                         # [F, 1]
    valid_bin = bins < nb                                          # [F, B]

    is_nan = missing_code[:, None] == 2
    is_zero = missing_code[:, None] == 1
    full_mode = (num_bins > 2) & (missing_code != 0)               # [F]

    # bins excluded from directional accumulation in full mode
    excl_full = (is_nan & (bins == nb - 1)) | (is_zero & (bins == default_bin[:, None]))
    excl = jnp.where(full_mode[:, None], excl_full, False) | ~valid_bin  # [F, B]
    inc = (~excl).astype(jnp.float32)[None, :, :]                  # [1, F, B]

    cum_g = jnp.cumsum(g * inc, axis=2)
    cum_h = jnp.cumsum(h * inc, axis=2)
    cum_c = jnp.cumsum(c * inc, axis=2)
    tot_g = cum_g[..., -1:]
    tot_h = cum_h[..., -1:]
    tot_c = cum_c[..., -1:]
    pg = parent_g[:, None, None]
    ph = parent_h[:, None, None]
    pc = parent_c[:, None, None]

    def child_gains(lg, lh, lc, rg, rh, rc):
        ok = ((lc >= min_data_in_leaf) & (rc >= min_data_in_leaf)
              & (lh >= min_sum_hessian_in_leaf) & (rh >= min_sum_hessian_in_leaf))
        gains = (leaf_split_gain(lg, lh, lambda_l1, lambda_l2)
                 + leaf_split_gain(rg, rh, lambda_l1, lambda_l2))
        return jnp.where(ok, gains, NEG_INF)

    # --- forward scan (dir=+1): left = included bins <= t, missing -> right
    fwd_lg, fwd_lh, fwd_lc = cum_g, cum_h, cum_c
    fwd_rg, fwd_rh, fwd_rc = pg - fwd_lg, ph - fwd_lh, pc - fwd_lc
    fwd_thr_ok = (full_mode[:, None]                                # fwd only in full mode
                  & (bins <= nb - 2)
                  & ~(is_zero & (bins == default_bin[:, None])))    # skip_default_bin
    fwd_gain = jnp.where(fwd_thr_ok[None], child_gains(fwd_lg, fwd_lh, fwd_lc,
                                                       fwd_rg, fwd_rh, fwd_rc), NEG_INF)

    # --- reverse scan (dir=-1): right = included bins > t, missing -> left
    rev_rg, rev_rh, rev_rc = tot_g - cum_g, tot_h - cum_h, tot_c - cum_c
    rev_lg, rev_lh, rev_lc = pg - rev_rg, ph - rev_rh, pc - rev_rc
    rev_max_thr = jnp.where(full_mode & (missing_code == 2), nb[:, 0] - 3, nb[:, 0] - 2)
    rev_thr_ok = ((bins <= rev_max_thr[:, None]) & (bins >= 0)
                  & ~(full_mode[:, None] & is_zero & (bins == default_bin[:, None] - 1)))
    rev_gain = jnp.where(rev_thr_ok[None], child_gains(rev_lg, rev_lh, rev_lc,
                                                       rev_rg, rev_rh, rev_rc), NEG_INF)

    # default direction: rev sends missing left, except the 2-bin NaN fix
    # (feature_histogram.hpp:96-98) where missing is the last bin on the right.
    rev_default_left = ~(~full_mode & (missing_code == 2))          # [F]

    feature_gate = jnp.where(feature_ok[None, :, None], 0.0, NEG_INF)
    parent_gain_shift = (leaf_split_gain(parent_g, parent_h, lambda_l1, lambda_l2)
                         + min_gain_to_split)[:, None, None]
    rev_gain = rev_gain + feature_gate
    fwd_gain = fwd_gain + feature_gate
    rev_gain = jnp.where(rev_gain > parent_gain_shift, rev_gain - parent_gain_shift, NEG_INF)
    fwd_gain = jnp.where(fwd_gain > parent_gain_shift, fwd_gain - parent_gain_shift, NEG_INF)

    # --- per feature: pick best over (dir, threshold); rev first to mirror the
    # reference's dir=-1-then-dir=+1 strict-improvement ordering (:89-93)
    dir_gain = jnp.stack([rev_gain, fwd_gain], axis=2)              # [S, F, 2, B]
    flat = dir_gain.reshape(S, F, 2 * B)
    best_idx = jnp.argmax(flat, axis=2)                             # [S, F]
    best_gain = jnp.take_along_axis(flat, best_idx[..., None], axis=2)[..., 0]
    is_rev = best_idx < B
    t_idx = (best_idx % B).astype(jnp.int32)

    def pick(rev_arr, fwd_arr):  # [S, F, B] -> [S, F] at t_idx per direction
        r = jnp.take_along_axis(rev_arr, t_idx[..., None], axis=2)[..., 0]
        f = jnp.take_along_axis(fwd_arr, t_idx[..., None], axis=2)[..., 0]
        return jnp.where(is_rev, r, f)

    return PerFeatureBest(
        gain=best_gain,
        threshold=t_idx,
        default_left=jnp.where(is_rev, rev_default_left[None, :], False),
        left_g=pick(rev_lg, fwd_lg),
        left_h=pick(rev_lh, fwd_lh),
        left_c=pick(rev_lc, fwd_lc),
    )


def unpack_bundled_hist(hist_g: jnp.ndarray, col: jnp.ndarray,
                        unpack_bin: jnp.ndarray,
                        pg: jnp.ndarray, ph: jnp.ndarray, pc: jnp.ndarray,
                        default_bin: jnp.ndarray) -> jnp.ndarray:
    """EFB unpack: [T, G, Bb, 3] bundle-space histograms -> [T, F, B, 3]
    original-feature space, reconstructing each feature's default bin by
    subtraction from the leaf totals (reference Dataset::FixHistogram,
    dataset.cpp:750-769 — applied per scanned feature there too).

    This is the LEGACY scan representation (``tpu_efb_unpack=true``, the
    A/B + parity arm): the default path never materializes the [T, F, B]
    decode — :func:`per_feature_best_bundled` scans the bundle-space
    histogram directly."""
    ub = unpack_bin                                  # [F, B]
    h = hist_g[:, col]                               # [T, F, Bb, 3]
    idx = jnp.maximum(ub, 0)[None, :, :, None]
    hf = jnp.take_along_axis(h, idx, axis=2)         # [T, F, B, 3]
    hf = jnp.where((ub >= 0)[None, :, :, None], hf, 0.0)
    totals = jnp.stack([pg, ph, pc], axis=-1)        # [T, 3]
    deficit = totals[:, None, :] - hf.sum(axis=2)    # [T, F, 3]
    F = ub.shape[0]
    return hf.at[:, jnp.arange(F), default_bin, :].add(deficit)


_BIG_T = 2 ** 30                # threshold sentinel for the min-scatter
                                # (plain int: jnp casts lazily at trace time
                                # — no import-time backend init, R006)


def per_feature_best_bundled(
    hist: jnp.ndarray,        # [T, G, Bb, 3] BUNDLE-space (sum_g, sum_h, cnt)
    parent_g: jnp.ndarray,    # [T]
    parent_h: jnp.ndarray,    # [T]
    parent_c: jnp.ndarray,    # [T]
    num_bins: jnp.ndarray,    # [F] i32 (ORIGINAL feature space)
    missing_code: jnp.ndarray,  # [F] i32: 0=none, 1=zero, 2=nan
    default_bin: jnp.ndarray,   # [F] i32
    feature_ok: jnp.ndarray,    # [F] bool (numerical & feature_fraction mask)
    col: jnp.ndarray,         # [F] i32 bundled column of feature f
    lo: jnp.ndarray,          # [F] i32 first bundle code of f's range
    hi: jnp.ndarray,          # [F] i32 one-past-last bundle code
    off: jnp.ndarray,         # [F] i32 orig_bin = code - off inside [lo, hi)
    code_feat: jnp.ndarray,   # [G, Bb] i32 owner feature of each bundle
                              # code; -1 = unowned (code 0 / padding / the
                              # default-bin hole at off+db)
    *,
    lambda_l1: float,
    lambda_l2: float,
    min_data_in_leaf: float,
    min_sum_hessian_in_leaf: float,
    min_gain_to_split: float,
) -> PerFeatureBest:
    """Best numerical threshold per (slot, feature) WITHOUT leaving bundle
    space — the TPU analog of the reference finding splits on FeatureGroup
    bins natively (feature_histogram.hpp over the group-encoded histogram;
    it never unpacks a bundle either, src/io/dataset.cpp:750-769 only
    reconstructs the shared default bin by subtraction).

    The cumulative gain scan runs over the [G, Bb] bundle axis — G*Bb
    positions instead of the F*B the unpack path pays — and respects member
    boundaries through the BundlePlan lo/hi tables:

    - each owned code c of column g belongs to exactly one member feature
      ``code_feat[g, c]`` with original bin ``c - off[f]`` (EFB codes are
      monotone in the original bin, efb.py), so a per-column cumulative sum
      minus the member's base ``CC[lo-1]`` is the member's own prefix sum;
    - the shared default bin has no code: its mass is reconstructed per
      member as ``parent - (CC_raw[hi-1] - CC_raw[lo-1])`` (FixHistogram by
      subtraction, exactly what the unpack path's deficit computes) and
      spliced into every prefix at ``t >= default_bin``;
    - the default-bin THRESHOLD (t == db, which has no code position when
      the member's bin 0 is the default) is evaluated in a [T, F] side
      channel and merged with the per-code candidates.

    Tie-break order is pinned to the feature-space scan's flat argmax:
    within a feature, rev-direction candidates beat fwd on equal gain and
    the LOWEST threshold wins within a direction; across features the
    caller's `reduce_features` argmax keeps lowest-feature-index wins.
    Bit-identity with the unpack arm holds whenever the histogram sums are
    exactly representable (tests plant dyadic gradients for the pinned
    axes); on arbitrary float data the two arms differ only in summation
    order inside the cumulative sums.
    """
    T, G, Bb, _ = hist.shape
    F = num_bins.shape[0]
    iota_b = jnp.arange(Bb, dtype=jnp.int32)[None, :]              # [1, Bb]
    owned = code_feat >= 0
    cfs = jnp.where(owned, code_feat, 0)                           # safe idx
    # per-code owner metadata (gathers of [F] tables — G*Bb elements)
    nb_c = num_bins[cfs]
    mc_c = missing_code[cfs]
    db_c = default_bin[cfs]
    t_c = iota_b - off[cfs]                                        # orig bin
    full_c = (nb_c > 2) & (mc_c != 0)
    # codes excluded from directional accumulation (mirrors the
    # feature-space `excl_full`): the nan bin in full mode; the zero bin
    # never has a code (the owner rule drops c == off+db), so its clause
    # is vacuous here but kept for symmetry with the unpack path
    excl_c = full_c & (((mc_c == 2) & (t_c == nb_c - 1))
                       | ((mc_c == 1) & (t_c == db_c)))
    inc_c = (owned & ~excl_c).astype(hist.dtype)
    raw_c = owned.astype(hist.dtype)
    # two code-axis cumulative sums: scan-included mass (drives the
    # threshold prefix sums) and raw owned mass (drives FixHistogram's
    # deficit — the unpack path sums ALL unpacked bins incl. the nan bin)
    CCs = jnp.cumsum(hist * inc_c[None, :, :, None], axis=2)
    CCu = jnp.cumsum(hist * raw_c[None, :, :, None], axis=2)
    flatS = CCs.reshape(T, G * Bb, 3)
    flatU = CCu.reshape(T, G * Bb, 3)

    def at_pos(flat, cpos):
        """CC value at per-feature column position [F] -> [T, F, 3];
        positions < 0 read as zero mass (a member starting at code 0)."""
        idx = col * Bb + jnp.clip(cpos, 0, Bb - 1)
        v = jnp.take(flat, idx, axis=1)
        return jnp.where((cpos >= 0)[None, :, None], v, 0.0)

    base_s = at_pos(flatS, lo - 1)                                 # [T, F, 3]
    base_u = at_pos(flatU, lo - 1)
    member_u = at_pos(flatU, hi - 1) - base_u      # raw non-default mass
    fullF = (num_bins > 2) & (missing_code != 0)
    # deficit included in the accumulating scan unless the zero bin is
    # excluded in full mode (skip_default_bin's accumulation half)
    dincF = ~(fullF & (missing_code == 1))
    totals = jnp.stack([parent_g, parent_h, parent_c], axis=-1)[:, None, :]
    deficit = totals - member_u                                    # [T, F, 3]
    def_inc = jnp.where(dincF[None, :, None], deficit, 0.0)
    tot_f = (at_pos(flatS, hi - 1) - base_s) + def_inc             # [T, F, 3]

    def per_code(fv):
        """Broadcast a [T, F, ...] per-feature value to code positions."""
        return jnp.take(fv, cfs.reshape(-1), axis=1).reshape(
            (T, G, Bb) + fv.shape[2:])

    # prefix sum at threshold t_c for the owning member: column cumsum
    # minus the member base, plus the reconstructed default-bin mass once
    # the prefix crosses it
    cum_c = (CCs - per_code(base_s)
             + jnp.where((t_c >= db_c)[None, :, :, None],
                         per_code(def_inc), 0.0))
    tot_c = per_code(tot_f)
    pg = parent_g[:, None, None]
    ph = parent_h[:, None, None]
    pc = parent_c[:, None, None]

    def child_gains(lg, lh, lc, rg, rh, rc):
        ok = ((lc >= min_data_in_leaf) & (rc >= min_data_in_leaf)
              & (lh >= min_sum_hessian_in_leaf)
              & (rh >= min_sum_hessian_in_leaf))
        gains = (leaf_split_gain(lg, lh, lambda_l1, lambda_l2)
                 + leaf_split_gain(rg, rh, lambda_l1, lambda_l2))
        return jnp.where(ok, gains, NEG_INF)

    lg_c, lh_c, lc_c = cum_c[..., 0], cum_c[..., 1], cum_c[..., 2]
    # --- forward (dir=+1): left = included bins <= t, missing -> right.
    # t == db never appears at an owned code, so skip_default_bin's
    # threshold half is structural here; the side channel re-checks it.
    fwd_ok_c = owned & full_c & (t_c <= nb_c - 2)
    fwd_gain_c = jnp.where(
        fwd_ok_c[None], child_gains(lg_c, lh_c, lc_c,
                                    pg - lg_c, ph - lh_c, pc - lc_c),
        NEG_INF)
    # --- reverse (dir=-1): right = included bins > t, missing -> left
    rev_r = tot_c - cum_c
    rg_c, rh_c, rc_c = rev_r[..., 0], rev_r[..., 1], rev_r[..., 2]
    rev_max_c = jnp.where(full_c & (mc_c == 2), nb_c - 3, nb_c - 2)
    rev_ok_c = (owned & (t_c <= rev_max_c) & (t_c >= 0)
                & ~(full_c & (mc_c == 1) & (t_c == db_c - 1)))
    rev_gain_c = jnp.where(
        rev_ok_c[None], child_gains(pg - rg_c, ph - rh_c, pc - rc_c,
                                    rg_c, rh_c, rc_c),
        NEG_INF)

    # --- per-feature reduction over the code grid: max gain, then the
    # LOWEST threshold achieving it (the flat-argmax first-occurrence rule)
    idxF = jnp.where(owned, code_feat, F).reshape(-1)              # [G*Bb]
    tflat = t_c.reshape(-1)

    def seg_best(gain_c):
        gflat = gain_c.reshape(T, G * Bb)
        mg = jnp.full((T, F + 1), NEG_INF, jnp.float32) \
            .at[:, idxF].max(gflat)[:, :F]
        back = jnp.take(mg, cfs.reshape(-1), axis=1)               # [T, G*Bb]
        tcand = jnp.where((gflat == back) & jnp.isfinite(gflat),
                          tflat[None, :], _BIG_T)
        bt = jnp.full((T, F + 1), _BIG_T, jnp.int32) \
            .at[:, idxF].min(tcand)[:, :F]
        return mg, bt

    # --- default-bin threshold side channel ([T, F]): t == db has no code
    # when the member's bin 0 is its default (EFB's shift), and is the
    # zero-mass hole otherwise — evaluate it directly from the same CC
    # gathers so its floats match the grid's construction
    dbF = default_bin
    cum_db = (at_pos(flatS, off + dbF) - base_s) + def_inc
    lgd, lhd, lcd = cum_db[..., 0], cum_db[..., 1], cum_db[..., 2]
    pgF, phF, pcF = (parent_g[:, None], parent_h[:, None], parent_c[:, None])
    fwd_db_ok = fullF & (dbF <= num_bins - 2) & (missing_code != 1)
    fwd_db_gain = jnp.where(
        fwd_db_ok[None], child_gains(lgd, lhd, lcd,
                                     pgF - lgd, phF - lhd, pcF - lcd),
        NEG_INF)
    rev_maxF = jnp.where(fullF & (missing_code == 2),
                         num_bins - 3, num_bins - 2)
    rev_db_ok = (dbF <= rev_maxF) & (dbF >= 0)
    rev_rd = tot_f - cum_db
    rgd, rhd, rcd = rev_rd[..., 0], rev_rd[..., 1], rev_rd[..., 2]
    rev_db_gain = jnp.where(
        rev_db_ok[None], child_gains(pgF - rgd, phF - rhd, pcF - rcd,
                                     rgd, rhd, rcd),
        NEG_INF)

    def combine(mg_bt, gdb):
        mg, bt = mg_bt
        use_db = (gdb > mg) | ((gdb == mg) & jnp.isfinite(gdb)
                               & (dbF[None, :] < bt))
        return (jnp.where(use_db, gdb, mg),
                jnp.where(use_db, dbF[None, :], bt))

    rev_g, rev_t = combine(seg_best(rev_gain_c), rev_db_gain)
    fwd_g, fwd_t = combine(seg_best(fwd_gain_c), fwd_db_gain)
    # rev first on ties — the feature-space [rev..., fwd...] flat argmax
    use_rev = rev_g >= fwd_g
    best_g = jnp.where(use_rev, rev_g, fwd_g)
    best_t = jnp.where(use_rev, rev_t, fwd_t).astype(jnp.int32)
    best_t = jnp.where(jnp.isfinite(best_g), best_t, 0)  # argmax's idx-0 rule

    # --- winner left sums, rebuilt from the SAME CC gathers the gains used
    p_win = off[None, :] + best_t                                  # [T, F]
    idx_win = col[None, :] * Bb + jnp.clip(p_win, 0, Bb - 1)
    cw = jnp.take_along_axis(
        flatS, jnp.broadcast_to(idx_win[:, :, None], (T, F, 3)), axis=1)
    cw = jnp.where((p_win >= 0)[..., None], cw, 0.0)
    cum_w = (cw - base_s) + jnp.where((best_t >= dbF[None, :])[..., None],
                                      def_inc, 0.0)
    rev_l = totals - (tot_f - cum_w)       # pg - rev_rg, the rev pick() path
    left = jnp.where(use_rev[..., None], rev_l, cum_w)

    feature_gate = jnp.where(feature_ok, 0.0, NEG_INF)[None, :]
    parent_gain_shift = (leaf_split_gain(parent_g, parent_h,
                                         lambda_l1, lambda_l2)
                         + min_gain_to_split)[:, None]
    best_g = best_g + feature_gate
    best_g = jnp.where(best_g > parent_gain_shift,
                       best_g - parent_gain_shift, NEG_INF)
    rev_dl = ~(~fullF & (missing_code == 2))
    return PerFeatureBest(
        gain=best_g,
        threshold=best_t,
        default_left=jnp.where(use_rev, rev_dl[None, :], False),
        left_g=left[..., 0],
        left_h=left[..., 1],
        left_c=left[..., 2],
    )


def reduce_features(pf: PerFeatureBest, feature_offset=0, is_cat=None,
                    cat_mask=None, num_bins_padded: int = 0) -> SplitCandidates:
    """Argmax over the feature axis -> one candidate per slot.

    ``feature_offset`` maps local feature indices to global ones when the
    caller holds only a feature shard (parallel/comm.py feature-parallel
    learner; reference feature_parallel_tree_learner.cpp:31-50).
    ``is_cat`` [F] / ``cat_mask`` [S, F, B] carry categorical left-sets
    (ops/categorical.py) through to the winner.
    """
    S, F = pf.gain.shape
    f_idx = jnp.argmax(pf.gain, axis=1)                             # [S]
    srange = jnp.arange(S)

    def gather(arr):
        return arr[srange, f_idx]

    if is_cat is None:
        B = num_bins_padded or 1
        win_cat = jnp.zeros(S, bool)
        win_mask = jnp.zeros((S, B), bool)
    else:
        win_cat = is_cat[f_idx]
        win_mask = cat_mask[srange, f_idx]                          # [S, B]

    return SplitCandidates(
        gain=gather(pf.gain),
        feature=(f_idx + feature_offset).astype(jnp.int32),
        threshold=gather(pf.threshold).astype(jnp.int32),
        default_left=gather(pf.default_left),
        left_g=gather(pf.left_g),
        left_h=gather(pf.left_h),
        left_c=gather(pf.left_c),
        is_cat=win_cat,
        cat_mask=win_mask,
    )


def find_best_splits_numerical(
    hist, parent_g, parent_h, parent_c, num_bins, missing_code, default_bin,
    feature_ok, **kwargs,
) -> SplitCandidates:
    """Single-shard numerical-only best split per slot (test/bench path)."""
    pf = per_feature_best_numerical(
        hist, parent_g, parent_h, parent_c, num_bins, missing_code,
        default_bin, feature_ok, **kwargs)
    return reduce_features(pf, num_bins_padded=hist.shape[2])
