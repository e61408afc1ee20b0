"""The one general generator of tabular training data.

A configuration's ``data`` group names column groups by their marginal and a
label as a signal over named columns plus noise; this file turns that and a
seed into ``(X [N, F] float32, y [N] float32)``. It imports nothing of the
program.

The CONTENT comes from ``data.base_seed``, fixed in the configuration: block
``b`` of ``block_rows`` rows is drawn from ``SeedSequence([base_seed, b])``.
The run's ``--seed`` decides the ORDER OF THE COLUMNS (a permutation of the
features; the label follows its columns). Every seed therefore trains on the
same table with its features in another order: the bin-finding sample picks
the same rows, every feature gets the same bins, the trees are the same up
to the features' numbering, and the work is the same, as the contract asks
of a seed. (A first version permuted the row blocks instead: the row sample
then differed, the bins with it, and the trees' shapes; train_rate spread by
1.2% between seeds while two runs of one seed agreed to four digits. PERF.md
Findings, PR 26.) Blocks are drawn by a few threads (numpy's generators
release the GIL), each writing its own rows.

Column kinds (all float32, dense, no missing values):
  count_zero   zero with probability ``p_zero``, else floor(lognormal(mu, sigma))
  rate         Beta(a, b) in [0, 1]
  powerlaw     floor(Pareto(alpha) * scale): a heavy-tailed count
"""
import concurrent.futures

import numpy as np

THREADS = 8


def _column(rng, kind: str, p: dict, n: int) -> np.ndarray:
    if kind == "count_zero":
        v = np.floor(rng.lognormal(p["mu"], p["sigma"], n))
        v[rng.random(n) < p["p_zero"]] = 0.0
        return v
    if kind == "rate":
        return rng.beta(p["a"], p["b"], n)
    if kind == "powerlaw":
        return np.floor(rng.pareto(p["alpha"], n) * p["scale"])
    raise ValueError(f"unknown column kind {kind!r}")


def num_features(data: dict) -> int:
    return sum(int(g["n"]) for g in data["columns"])


def _block(data: dict, b: int, n: int, place: np.ndarray):
    """Rows of block ``b``: ([n, F] f32, [n] f32); the configuration's column
    ``c`` is written to column ``place[c]``. The label is 1 where the signal
    plus logistic noise is positive."""
    rng = np.random.default_rng(np.random.SeedSequence([int(data["base_seed"]), b]))
    X = np.empty((n, num_features(data)), np.float32)
    c = 0
    for g in data["columns"]:
        for _ in range(int(g["n"])):
            X[:, place[c]] = _column(rng, g["kind"], g, n)
            c += 1
    lab = data["label"]
    z = np.full(n, float(lab["bias"]))
    for t in lab["terms"]:
        v = np.ones(n)
        for c in t["cols"]:
            x = X[:, place[c]].astype(np.float64)
            v = v * (np.log1p(x) if t.get("log1p") else x)
        z += float(t["w"]) * v
    z += rng.logistic(0.0, float(lab["noise"]), n)
    return X, (z > 0).astype(np.float32)


def column_places(data: dict, seed: int) -> np.ndarray:
    """Where each of the configuration's columns lands for this seed."""
    return np.random.default_rng(int(seed)).permutation(num_features(data))


def generate(data: dict, rows: int, seed: int):
    """(X, y) for a run: contents from the configuration's base seed, the
    order of the columns from ``seed``."""
    br = int(data["block_rows"])
    nb = -(-rows // br)
    place = column_places(data, seed)
    X = np.empty((rows, num_features(data)), np.float32)
    y = np.empty(rows, np.float32)

    def fill(b: int) -> None:
        lo = b * br
        hi = min(lo + br, rows)
        X[lo:hi], y[lo:hi] = _block(data, b, hi - lo, place)

    with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
        for f in [pool.submit(fill, b) for b in range(nb)]:
            f.result()
    return X, y
