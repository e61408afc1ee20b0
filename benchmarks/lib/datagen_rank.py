"""A learning-to-rank table: query-grouped rows with graded labels.

The feature columns are ``lib/datagen.py``'s, called and not changed: the
same column kinds, the same fixed content from ``data.base_seed`` and the
same ORDER OF THE COLUMNS from ``--seed``. What this file adds, all of it
from ``data.base_seed`` and none of it from the run's seed (so every seed
trains the same queries, labels and trees up to the features' numbering):

  queries  ``data.queries``: ``count`` queries over all rows, contiguous, in
           row order; lengths drawn lognormal with mean ``mean`` and shape
           ``sigma``, rounded, kept in [``min``, ``max``], then rescaled and
           nudged one doc at a time until they sum to the rows exactly
  grades   ``data.grades``: a per-doc signal (``terms``, each a weight times
           a standardised column, log1p'd where asked) plus a per-query
           offset (normal, ``query_offset``) plus noise (normal, ``noise``),
           cut at its own quantiles so that grade k holds ``shares[k]`` of
           the rows, to the row

``datagen.generate`` also makes a 0/1 label of its own, from ``data.label``;
it is drawn and dropped.
"""
import numpy as np

from . import datagen


def query_sizes(queries: dict, rows: int, base_seed: int) -> np.ndarray:
    """Lengths of ``queries["count"]`` contiguous queries that sum to ``rows``."""
    n = int(queries["count"])
    lo, hi = int(queries["min"]), int(queries["max"])
    if not n * lo <= rows <= n * hi:
        raise ValueError(f"{n} queries of {lo}..{hi} docs cannot hold {rows} rows")
    rng = np.random.default_rng(np.random.SeedSequence([int(base_seed), 1 << 20]))
    sigma = float(queries["sigma"])
    mu = np.log(float(queries["mean"])) - sigma * sigma / 2.0
    raw = rng.lognormal(mu, sigma, n)
    sizes = np.clip(np.rint(raw), lo, hi).astype(np.int64)
    for _ in range(64):                   # rescale the ones with room to move
        gap = rows - int(sizes.sum())
        if gap == 0:
            break
        free = sizes > lo if gap < 0 else sizes < hi
        if not free.any():
            break
        scale = 1.0 + gap / float(sizes[free].sum())
        sizes[free] = np.clip(np.rint(sizes[free] * scale), lo, hi)
    gap = rows - int(sizes.sum())
    step = 1 if gap > 0 else -1
    # what rounding left: one doc more (or fewer) for the longest queries
    # that have room, in a fixed order
    for q in np.argsort(-raw, kind="stable"):
        if gap == 0:
            break
        if lo <= sizes[q] + step <= hi:
            sizes[q] += step
            gap -= step
    if gap:
        raise RuntimeError(f"query lengths miss the rows by {gap}")
    return sizes


def grades(data: dict, X: np.ndarray, place: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Graded labels 0..len(shares)-1, float32, as the head of this file says."""
    g = data["grades"]
    rows = X.shape[0]
    rng = np.random.default_rng(np.random.SeedSequence([int(data["base_seed"]), 1 << 21]))
    z = np.zeros(rows)
    for t in g["terms"]:
        v = X[:, place[int(t["col"])]].astype(np.float64)
        if t.get("log1p"):
            v = np.log1p(v)
        z += float(t["w"]) * (v - v.mean()) / max(float(v.std()), 1e-12)
    z += np.repeat(rng.normal(0.0, float(g["query_offset"]), len(sizes)), sizes)
    z += rng.normal(0.0, float(g["noise"]), rows)
    shares = np.asarray(g["shares"], np.float64)
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError(f"grade shares sum to {shares.sum()}")
    # grade k for the rows ranked in its share, counted from the bottom
    ends = np.rint(np.cumsum(shares) * rows).astype(np.int64)
    label = np.empty(rows, np.float32)
    order = np.argsort(z, kind="stable")
    start = 0
    for k, end in enumerate(ends):
        label[order[start:end]] = k
        start = end
    return label


def generate(data: dict, rows: int, seed: int):
    """(X [rows, F] float32, label [rows] float32, query sizes [Q] int64)."""
    X, _ = datagen.generate(data, rows, seed)
    sizes = query_sizes(data["queries"], rows, int(data["base_seed"]))
    place = datagen.column_places(data, seed)
    return X, grades(data, X, place, sizes), sizes
