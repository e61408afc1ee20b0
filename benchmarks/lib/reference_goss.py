"""Plain reference for ONE tree grown under GOSS (gradient-based one-side
sampling; Ke et al., NeurIPS 2017, Algorithm 2; LightGBM src/boosting/
goss.hpp): NumPy, float64. It imports nothing of the program; from
``lib/reference.py`` it takes the routing, the walk, the sigmoid and the
exact node scan.

What it is given of the program: the tree under test, the resident score of
every row before the tree's dispatch (the step's input, as the rows are) and
the tree's 0/1 INCLUSION mask, nothing else about the sample. From the score
it computes its OWN g, h and |g*h|, its own ``top_k``-th largest value and
its own ``(N - top_k) / other_k``, and decides itself which included rows
are top rows (weight 1) and which were drawn from the rest (amplified):

    top_k = int(N * top_rate), other_k = int(N * other_rate)   (at least 1)
    top set   = the top_k rows of largest |g*h|, ties to the lower row number
    other set = a uniform draw of the rest at rate other_k / (N - top_k)
    g, h of the other set times (N - top_k) / other_k

It then values the tree as ``reference.follow`` does: routes EVERY row by
raw value, sums the weighted g and h of the included rows per leaf, counts
included rows per leaf and node (what ``min_data_in_leaf`` counts under a
sample), derives leaf values and gains, and adds the leaf values to the
score of ALL rows: an out-of-sample row is scored by the tree it did not
help to grow.

Two numbers of its own, beside those ``compare.numbers`` takes:

  top_missed       rows whose |g*h| lies clearly above the threshold (by more
                   than the tie band) and that the program left out. Exact:
                   limit 0. A program that takes its top set by |g| alone, or
                   drops part of the sample, reads thousands.
  other_count_gap  included rows clearly UNDER the threshold against the
                   binomial draw they have to be: |count - expected| over
                   five standard deviations, over all of the rest and over
                   its lower half by |g*h| (a "draw" that takes the rows
                   just under the threshold is no draw); the larger of the
                   two. Limit 1.

**The tie band.** The program ranks float32 products, this file float64
ones: rows within ``TIE_BAND`` (relative) of the threshold may fall on
either side of it in the program, legitimately (on the v5e the two products
differ by up to 5.3e-6 near the threshold; 13-17 included rows of 14.7M lay
within 4e-6 of it and up to 6 of them read better on the other side; my
chip runs, PR 36). They are left out of ``top_missed`` and of
``other_count_gap``. An included row of the band is a top row to one side
and an amplified row to the other, a difference of ``amplify - 1`` times its
g and h in ONE leaf (1e-3 to 1e-1 of a leaf value, by the leaf's size). So
where a leaf that holds band rows misses the tree's own value by
more than ``RESOLVE_TOL`` under the reference's own reading, the reference
tries both readings of its band rows (the ``BAND_SEARCH`` nearest the
threshold) and keeps the one nearest the tree's own leaf value.
``band_rows`` and ``band_flips`` say how many rows that touched; everything
else (gains, counts, scores) follows from the kept reading. What this gives
away: in those few leaves of one tree a program's error can hide behind a
flip only if it is within the tolerance of a multiple of ``7 g`` of a band
row; the leaf's remaining gap is still held by ``leaf_gap_max``.

``precision="bf16"`` rounds every g and h to bfloat16 before it is weighted
and summed (the control); ``rows_kept`` < 1 leaves the tail of every chunk
out of the sums (the "half of the batch" fault). ``draw_sample`` makes a
sample the way the definition above says, or a planted fault's way, for the
tests and the readings that need a mask no program made.
"""
import concurrent.futures
import itertools

import numpy as np

from . import reference
from .reference import THREADS

TIE_BAND = 1e-4       # relative: far outside what float32 moves the product
RESOLVE_TOL = 1e-4    # a leaf nearer than this to the tree's own is settled
BAND_SEARCH = 10      # included band rows a leaf whose readings are tried


def gradients(score: np.ndarray, y: np.ndarray):
    p = reference.sigmoid(np.asarray(score, np.float64))
    return p - y, p * (1.0 - p)


def counts(n: int, top_rate: float, other_rate: float):
    """(top_k, other_k, amplify) of ``n`` rows, as goss.hpp counts them."""
    top_k = max(1, int(n * top_rate))
    other_k = max(1, int(n * other_rate))
    return top_k, other_k, (n - top_k) / other_k


def top_set(w: np.ndarray, top_k: int):
    """(mask of the ``top_k`` rows of largest ``w``, ties to the lower row
    number; the threshold: the ``top_k``-th largest value)."""
    n = len(w)
    thr = np.partition(w, n - top_k)[n - top_k]
    top = w > thr
    ties = np.flatnonzero(w == thr)
    top[ties[: top_k - int(top.sum())]] = True
    return top, float(thr)


def draw_sample(score, y, *, top_rate: float, other_rate: float, seed: int,
                top_by: str = "gh", kept: float = 1.0) -> np.ndarray:
    """A 0/1 inclusion mask drawn as the definition says. Planted faults:
    ``top_by="g"`` ranks by |g| alone; ``kept`` < 1 drops that share of the
    sample again (every second row of it at 0.5)."""
    g, h = gradients(score, y)
    n = len(g)
    top_k, other_k, _ = counts(n, top_rate, other_rate)
    top, _ = top_set(np.abs(g * h) if top_by == "gh" else np.abs(g), top_k)
    u = np.random.default_rng([int(seed), 7]).random(n)
    included = top | (~top & (u < other_k / max(n - top_k, 1)))
    if kept < 1.0:
        idx = np.flatnonzero(included)
        included[idx[np.arange(len(idx)) * kept % 1.0 >= kept]] = False
    return included


def read_sample(w: np.ndarray, included: np.ndarray, top_rate: float,
                other_rate: float, tie_band: float = TIE_BAND) -> dict:
    """The reference's own reading of a program's inclusion mask."""
    n = len(w)
    top_k, other_k, amplify = counts(n, top_rate, other_rate)
    top, thr = top_set(w, top_k)
    above, under = w > thr * (1.0 + tie_band), w < thr * (1.0 - tie_band)
    prob = other_k / max(n - top_k, 1)
    gap = 0.0
    for rest in (under, under & (w < np.median(w[under]))):
        m = int(rest.sum())
        width = 5.0 * np.sqrt(max(m * prob * (1.0 - prob), 1.0))
        gap = max(gap, abs(int((included & rest).sum()) - m * prob) / width)
    return {"top": top & included, "other": included & ~top,
            "band": included & ~above & ~under, "amplify": amplify,
            "threshold": thr, "top_k": top_k, "other_k": other_k,
            "top_missed": float((above & ~included).sum()),
            "other_count_gap": float(gap)}


def _resolve_band(leaf, band_idx, dist, g, h, mult, G, H, tree, learning_rate,
                  lambda_l2, amplify) -> int:
    """For each leaf that holds included band rows and misses the tree's own
    value under the reference's reading, keep the reading of those rows (top
    or amplified, each; the ``BAND_SEARCH`` nearest the threshold by
    ``dist``) whose leaf value is nearest the tree's own. ``mult``, ``G`` and
    ``H`` are updated in place. Returns the rows whose reading changed."""
    flips = 0
    own_value = np.asarray(tree["leaf_value"], np.float64)
    value = -learning_rate * G / (H + lambda_l2)
    floor = float(np.median(np.abs(value)))
    for lf in np.unique(leaf[band_idx]):
        if abs(value[lf] - own_value[lf]) <= RESOLVE_TOL * max(abs(value[lf]), floor):
            continue
        rows = band_idx[leaf[band_idx] == lf]
        rows = rows[np.argsort(dist[rows], kind="stable")[:BAND_SEARCH]]
        g0 = G[lf] - np.sum(g[rows] * mult[rows])
        h0 = H[lf] - np.sum(h[rows] * mult[rows])
        best = None
        for reading in itertools.product((1.0, amplify), repeat=len(rows)):
            m = np.asarray(reading)
            gs, hs = g0 + np.sum(g[rows] * m), h0 + np.sum(h[rows] * m)
            miss = abs(-learning_rate * gs / (hs + lambda_l2) - own_value[lf])
            if best is None or miss < best[0]:
                best = (miss, m, gs, hs)
        flips += int(np.sum(best[1] != mult[rows]))
        mult[rows], G[lf], H[lf] = best[1], best[2], best[3]
    return flips


def value_tree(X: np.ndarray, y: np.ndarray, tree: dict, score_before,
               included, *, learning_rate: float, lambda_l2: float,
               top_rate: float, other_rate: float, sample: np.ndarray,
               scan: dict = None, precision: str = "f64",
               rows_kept: float = 1.0, amplified: bool = True,
               score_out_of_sample: bool = True, tie_band: float = TIE_BAND,
               threads: int = THREADS) -> dict:
    """Value one sampled ``tree`` (its structure; its leaf values only to
    settle the tie band) over all rows of ``X`` from ``score_before``, given
    the program's inclusion mask of that tree. Returns what
    ``reference.follow`` returns for one tree (lists of length 1) and the
    sample's numbers. ``amplified=False`` and ``score_out_of_sample=False``
    plant the two faults of those names."""
    n = X.shape[0]
    included = np.asarray(included).astype(bool)[:n]
    score = np.asarray(score_before, np.float64)[:n].copy()
    g, h = gradients(score, y)
    w = np.abs(g * h)
    read = read_sample(w, included, top_rate, other_rate, tie_band)
    if precision == "bf16":
        g, h = reference._round_bf16(g), reference._round_bf16(h)
    elif precision != "f64":
        raise ValueError(f"unknown precision {precision!r}")
    amplify = read["amplify"] if amplified else 1.0
    mult = np.where(read["top"], 1.0, amplify) * included
    L = int(tree["num_leaves"])
    feats = reference.used_features([tree])
    nchunk = max(1, min(threads, n // 4096))
    bounds = np.linspace(0, n, nchunk + 1).astype(np.int64)
    with concurrent.futures.ThreadPoolExecutor(nchunk) as pool:
        leaf = np.concatenate(list(pool.map(
            lambda c: reference.route(
                reference._columns(X, bounds[c], bounds[c + 1], feats),
                bounds[c + 1] - bounds[c], tree), range(nchunk))))
        summed = np.ones(n, bool)
        if rows_kept < 1.0:
            for lo, hi in zip(bounds[:-1], bounds[1:]):
                summed[lo + max(1, int(round((hi - lo) * rows_kept))):hi] = False
        ws = mult * summed
        G = np.bincount(leaf, weights=g * ws, minlength=L)
        H = np.bincount(leaf, weights=h * ws, minlength=L)
        cnt = np.bincount(leaf[included], minlength=L)
        band_idx = np.flatnonzero(read["band"])
        flips = 0
        if amplified and rows_kept >= 1.0 and precision == "f64":
            flips = _resolve_band(leaf, band_idx, np.abs(w / read["threshold"] - 1.0),
                                  g, h, mult, G, H, tree, learning_rate,
                                  lambda_l2, amplify)
        value = -learning_rate * G / (H + lambda_l2)
        GN, HN = reference.subtree_sums(tree, G), reference.subtree_sums(tree, H)
        gain = np.zeros(L - 1)
        for node in range(L - 1):
            lc, rc = int(tree["left_child"][node]), int(tree["right_child"][node])
            gl, hl = reference.child_sum(lc, G, GN), reference.child_sum(lc, H, HN)
            gr, hr = reference.child_sum(rc, G, GN), reference.child_sum(rc, H, HN)
            gain[node] = (gl * gl / (hl + lambda_l2) + gr * gr / (hr + lambda_l2)
                          - GN[node] ** 2 / (HN[node] + lambda_l2))
        node_cnt = reference.subtree_sums(tree, cnt)
        scans = None
        if scan:
            scans = _scan_nodes(X, tree, leaf, included, g * mult, h * mult,
                                node_cnt, gain, lambda_l2, scan, pool)
    score += np.where(included | score_out_of_sample, value[leaf], 0.0)
    return {"leaf_value": [value], "leaf_count": [cnt], "node_count": [node_cnt],
            "gain": [gain], "sample_score": [score[sample].copy()],
            "node_scan": [scans] if scan else [],
            "sample": {"top_missed": read["top_missed"],
                       "other_count_gap": read["other_count_gap"],
                       "rows_top": int(read["top"].sum()),
                       "rows_other": int(read["other"].sum()),
                       "band_rows": int(len(band_idx)), "band_flips": flips,
                       "amplify": read["amplify"], "threshold": read["threshold"],
                       "top_k": read["top_k"], "other_k": read["other_k"],
                       "sample_share_out": float(1.0 - included[sample].mean())}}


def _scan_nodes(X, tree, leaf, included, gw, hw, node_cnt, gain, lambda_l2,
                scan, pool):
    """``reference._scan_nodes`` over the INCLUDED rows under a node, with
    their weighted g and h: [worst split loss, least runner-up loss, nodes
    scanned]. ``scan["rows"]`` and ``min_side`` count included rows, as the
    program's ``min_data_in_leaf`` does under a sample."""
    least, most = scan["rows"]
    eligible = np.flatnonzero((node_cnt >= least) & (node_cnt <= most))
    pick = np.random.default_rng([int(scan["seed"]), 3])
    worst, runner_up, scanned = 0.0, np.inf, 0
    for node in pick.permutation(eligible)[: int(scan["nodes"])]:
        idx = np.flatnonzero(reference.leaves_under(tree, int(node))[leaf] & included)
        rows, g, h = X[idx], gw[idx], hw[idx]
        ranked = sorted(pool.map(
            lambda f: reference._best_gain_of_feature(
                np.ascontiguousarray(rows[:, f]), g, h, lambda_l2,
                int(scan["min_side"])), range(rows.shape[1])), reverse=True)
        best = max(ranked[0], float(gain[node]))
        worst = max(worst, 1.0 - float(gain[node]) / best)
        runner_up = min(runner_up, 1.0 - ranked[1] / best)
        scanned += 1
    return [worst, float(runner_up), scanned]
