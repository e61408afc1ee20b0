"""Plain reference for LambdaMART's first trees on a query-grouped table:
the lambdarank gradients (NDCG, the pairwise objective of the reference's
``rank_objective.hpp``), and leaves valued from them. It imports nothing of
the program and takes nothing the program made but the trees under test and,
for the steady tree, the program's score before it.

The gradients, per query, as the reference's pair loop has them: the docs
sorted by score, DESCENDING, tied scores in doc order (the reference's
``std::sort`` leaves ties unspecified; this is the one stated departure, and
it matters at the first tree, where every score is 0); for every pair with
the high doc's label above the low doc's

    ds     = s_high - s_low
    dNDCG  = (gain[l_high] - gain[l_low]) * |disc(rank_high) - disc(rank_low)|
             * inverse max DCG at max_position;  / (0.01 + |ds|) unless the
             query's best score equals its worst
    p      = 2 / (1 + exp(2 sigma ds));  lambda = -p dNDCG;  hess = 2 p (2-p) dNDCG
    g_high += lambda, g_low -= lambda;  h_high += hess, h_low += hess

with disc(r) = 1 / log2(2 + r) at the rank r in the sorted order and gain
the label gain table (2^l - 1 by default).

It is computed in two independent ways:

(a) ``query_gradients``: NumPy float64, one query at a time, the high docs
    in blocks of rows against all the query's docs. Used on a seeded sample
    of queries (``sample_queries``), against the program's own grad/hess
    (``grad_gap``).
(b) ``DeviceGradients``: plain ``jax.numpy`` in float32 (matmul precision
    "highest"; the pair block has no matmul), for EVERY doc, a block of
    queries at a time. No sort, no gather and no scatter on the device (the
    host lays the scores, labels and gains out by query): a doc's
    rank is the number of docs of its query ahead of it (a higher score, or
    the same score and an earlier doc), counted over the same pair block.
    Queries are padded to the next length of a grid (48, 96, 192, ..., the
    longest query's length) and a block holds as many as ``BLOCK_SLOTS`` pair
    slots allow. Its gradients value the leaves of the followed trees and of
    the steady tree through ``lib/reference.py``'s routing and leaf
    machinery (``value_trees``), summed per leaf in float64.

``precision="bf16"`` computes (b)'s pair block in bfloat16, the partners'
sums in float32: the control, which every limit has to refuse.
"""
import concurrent.futures

import numpy as np

from . import reference

THREADS = reference.THREADS
BLOCK_SLOTS = 1 << 24          # pair slots of one device block in (b)
HIGH_ROWS = 256                # high docs a NumPy block in (a)


def bounds(sizes: np.ndarray) -> np.ndarray:
    """Query boundaries: query q holds rows [qb[q], qb[q+1])."""
    return np.concatenate([[0], np.cumsum(np.asarray(sizes, np.int64))])


def inverse_max_dcg(labels: np.ndarray, gains: np.ndarray, k: int) -> float:
    """1 / DCG of the query's best order over its first k places (0 if 0)."""
    best = np.sort(np.asarray(labels, np.int64))[::-1][:k]
    dcg = float(np.sum(gains[best] / np.log2(np.arange(len(best)) + 2.0)))
    return 1.0 / dcg if dcg > 0.0 else 0.0


def query_gradients(score, label, *, gains, sigma: float, max_position: int):
    """(g, h) float64 of one query's docs, in doc order: form (a)."""
    s0 = np.asarray(score, np.float64)
    n = len(s0)
    order = np.argsort(-s0, kind="stable")
    s, lab = s0[order], np.asarray(label, np.int64)[order]
    imd = inverse_max_dcg(lab, gains, max_position)
    disc = 1.0 / np.log2(np.arange(n) + 2.0)
    gain = gains[lab]
    spread = s[0] != s[-1]
    g, h = np.zeros(n), np.zeros(n)
    with np.errstate(over="ignore"):
        for i0 in range(0, n, HIGH_ROWS):
            i = slice(i0, min(i0 + HIGH_ROWS, n))
            ds = s[i, None] - s[None, :]
            dndcg = (gain[i, None] - gain[None, :]) * np.abs(disc[i, None] - disc[None, :]) * imd
            if spread:
                dndcg = dndcg / (0.01 + np.abs(ds))
            p = 2.0 / (1.0 + np.exp(2.0 * sigma * ds))
            ok = lab[i, None] > lab[None, :]
            lam = np.where(ok, -p * dndcg, 0.0)
            hes = np.where(ok, 2.0 * p * (2.0 - p) * dndcg, 0.0)
            g[i] += lam.sum(axis=1)
            g -= lam.sum(axis=0)
            h[i] += hes.sum(axis=1)
            h += hes.sum(axis=0)
    back = np.argsort(order)            # sorted place of each doc
    return g[back], h[back]


def sample_queries(sizes: np.ndarray, seed: int, count: int, per_class: int) -> np.ndarray:
    """Query ids, sorted: the longest query, ``per_class`` drawn from each
    power-of-two length class (all of a class that holds fewer), and draws
    from the rest until ``count`` are taken. Drawn from ``seed``."""
    sizes = np.asarray(sizes)
    rng = np.random.default_rng([int(seed), 3])
    cls = np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64)
    take = {int(np.argmax(sizes))}
    for c in np.unique(cls):
        members = np.flatnonzero(cls == c)
        take.update(int(q) for q in rng.choice(members, min(per_class, len(members)),
                                               replace=False))
    rest = np.setdiff1d(np.arange(len(sizes)), np.fromiter(take, np.int64))
    more = max(0, int(count) - len(take))
    take.update(int(q) for q in rng.choice(rest, min(more, len(rest)), replace=False))
    return np.sort(np.fromiter(take, np.int64))


def sample_rows(sizes: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """The rows of ``queries``, in row order."""
    qb = bounds(sizes)
    return np.concatenate([np.arange(qb[q], qb[q + 1]) for q in queries])


def sample_gradients(score_rows, label, sizes, queries, *, gains, sigma, max_position,
                     threads: int = THREADS):
    """Form (a) over the sampled queries: (g, h) float64 at their rows in
    row order; ``score_rows`` and ``label`` are given at those rows."""
    sizes = np.asarray(sizes)
    ends = np.cumsum(sizes[queries])
    starts = ends - sizes[queries]

    def one(k):
        lo, hi = starts[k], ends[k]
        return query_gradients(score_rows[lo:hi], label[lo:hi], gains=gains,
                               sigma=sigma, max_position=max_position)

    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        parts = list(pool.map(one, range(len(queries))))
    return (np.concatenate([p[0] for p in parts]), np.concatenate([p[1] for p in parts]))


def grad_gap(prog, ref, sizes, queries) -> float:
    """Worst sampled query: max |prog - ref| over the query's max |ref|
    (queries whose reference is all zero are held to |prog| = 0)."""
    sizes = np.asarray(sizes)
    ends = np.cumsum(sizes[queries])
    worst = 0.0
    for lo, hi in zip(ends - sizes[queries], ends):
        d = float(np.max(np.abs(prog[lo:hi] - ref[lo:hi])))
        top = float(np.max(np.abs(ref[lo:hi])))
        worst = max(worst, d / top if top > 0.0 else (0.0 if d == 0.0 else np.inf))
    return worst


def length_grid(longest: int) -> list:
    """Padded query lengths of (b): 48, 96, 192, ... (each twice the one
    before), the last one the longest query's own length. Few lengths, few
    compiles: each is one jitted function's shape."""
    grid = [48]
    while grid[-1] < longest:
        grid.append(grid[-1] * 2)
    grid[-1] = max(int(longest), 48)
    return grid


class DeviceGradients:
    """Form (b): every doc's (g, h), float64 on return, from a score vector.

    The queries are laid out once: by padded length, ``rows`` queries a
    block, the blocks of one length stacked so that one call of a jitted
    function walks them (``lax.map``). A call per padded length and score."""

    def __init__(self, sizes, label, *, gains, sigma: float, max_position: int,
                 precision: str = "f32"):
        import jax
        import jax.numpy as jnp
        if precision not in ("f32", "bf16"):
            raise ValueError(f"unknown precision {precision!r}")
        sizes = np.asarray(sizes, np.int64)
        qb = bounds(sizes)
        self.n = int(qb[-1])
        label = np.asarray(label, np.int64)
        grid = length_grid(int(sizes.max()))
        padded = np.asarray(grid)[np.searchsorted(grid, sizes)]
        self.layouts = []
        for L in grid:
            qs = np.flatnonzero(padded == L)
            if not len(qs):
                continue
            rows = max(1, BLOCK_SLOTS // (L * L))
            nblk = -(-len(qs) // rows)
            idx = np.full((nblk * rows, L), self.n, np.int64)   # sentinel: a pad doc
            imd = np.zeros(nblk * rows)
            for r, q in enumerate(qs):
                idx[r, : sizes[q]] = np.arange(qb[q], qb[q + 1])
                imd[r] = inverse_max_dcg(label[qb[q]:qb[q + 1]], gains, max_position)
            lab = np.append(label, 0)[idx]
            self.layouts.append({
                "idx": idx, "real": idx < self.n,
                "lab": jnp.asarray(lab.reshape(nblk, rows, L).astype(np.int32)),
                "gain": jnp.asarray(np.asarray(gains, np.float32)[lab].reshape(nblk, rows, L)),
                "valid": jnp.asarray((idx < self.n).reshape(nblk, rows, L)),
                "imd": jnp.asarray(imd.reshape(nblk, rows).astype(np.float32))})
        pair_dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32

        def block(s, lab, gain, valid, imd):
            # s, lab, gain, valid [rows, L]; imd [rows]
            L = s.shape[-1]
            pos = jnp.arange(L)
            ahead = ((s[:, None, :] > s[:, :, None])
                     | ((s[:, None, :] == s[:, :, None]) & (pos[None, :] < pos[:, None])))
            rank = jnp.sum(ahead & valid[:, None, :], axis=-1)            # [rows, L]
            disc = 1.0 / jnp.log2(rank.astype(jnp.float32) + 2.0)
            big = jnp.float32(3e38)
            best = jnp.max(jnp.where(valid, s, -big), axis=-1)
            worst = jnp.min(jnp.where(valid, s, big), axis=-1)
            spread = (best != worst)[:, None, None]
            c = lambda a: jnp.asarray(a).astype(pair_dtype)               # noqa: E731
            ds = c(s[:, :, None]) - c(s[:, None, :])                      # high i, low j
            dndcg = ((c(gain[:, :, None]) - c(gain[:, None, :]))
                     * jnp.abs(c(disc[:, :, None]) - c(disc[:, None, :]))
                     * c(imd[:, None, None]))
            dndcg = jnp.where(spread, dndcg / (c(0.01) + jnp.abs(ds)), dndcg)
            p = c(2.0) / (c(1.0) + jnp.exp(c(2.0 * sigma) * ds))
            ok = (lab[:, :, None] > lab[:, None, :]) & valid[:, :, None] & valid[:, None, :]
            lam = jnp.where(ok, -p * dndcg, c(0.0)).astype(jnp.float32)
            hes = jnp.where(ok, c(2.0) * p * (c(2.0) - p) * dndcg, c(0.0)).astype(jnp.float32)
            return (lam.sum(axis=2) - lam.sum(axis=1), hes.sum(axis=2) + hes.sum(axis=1))

        def stacked(s, lab, gain, valid, imd):
            return jax.lax.map(lambda a: block(*a), (s, lab, gain, valid, imd))

        self._fn = jax.jit(stacked)
        self._jnp = jnp
        self._jax = jax

    def __call__(self, score):
        """(g, h) float64 [n] at ``score`` (every row, row order)."""
        jax, jnp = self._jax, self._jnp
        s_ext = np.append(np.asarray(score, np.float32), np.float32(0.0))
        g, h = np.zeros(self.n), np.zeros(self.n)
        with jax.default_matmul_precision("highest"):
            for lay in self.layouts:
                s = jnp.asarray(s_ext[lay["idx"]].reshape(lay["lab"].shape))
                gq, hq = jax.device_get(self._fn(s, lay["lab"], lay["gain"], lay["valid"],
                                                 lay["imd"]))
                real = lay["real"]
                g[lay["idx"][real]] = np.asarray(gq, np.float64).reshape(real.shape)[real]
                h[lay["idx"][real]] = np.asarray(hq, np.float64).reshape(real.shape)[real]
        return g, h


def value_trees(X, trees, gradients, *, learning_rate: float, lambda_l2: float,
                init_score: float, sample: np.ndarray, restart: dict = None,
                threads: int = THREADS) -> dict:
    """``lib/reference.follow`` for a gradient that couples rows: per tree,
    (g, h) of every row come from ``gradients(score)`` (form (b)) at the
    reference's own running score, or at ``restart[t]`` where given; rows
    are routed by raw value in chunks (``reference.route``), g and h summed
    per leaf in float64, and the leaves valued -lr G / (H + lambda_l2).
    Returns per tree leaf values, leaf and node counts, gains, and the
    scores at the ``sample`` rows after it; and the running score's
    gradients per tree under ``grads``."""
    n = X.shape[0]
    feats = reference.used_features(trees)
    nchunk = max(1, min(threads, n // 4096))
    edges = np.linspace(0, n, nchunk + 1).astype(np.int64)
    score = np.full(n, float(init_score))
    out = {"leaf_value": [], "leaf_count": [], "node_count": [], "gain": [],
           "sample_score": []}
    with concurrent.futures.ThreadPoolExecutor(nchunk) as pool:
        cols = list(pool.map(
            lambda c: reference._columns(X, edges[c], edges[c + 1], feats), range(nchunk)))
        for t, tree in enumerate(trees):
            L = int(tree["num_leaves"])
            if restart and t in restart:
                score = np.asarray(restart[t], np.float64).copy()
            g, h = gradients(score)

            def part(c):
                lo, hi = edges[c], edges[c + 1]
                leaf = reference.route(cols[c], hi - lo, tree)
                return (leaf, np.bincount(leaf, weights=g[lo:hi], minlength=L),
                        np.bincount(leaf, weights=h[lo:hi], minlength=L),
                        np.bincount(leaf, minlength=L))

            parts = list(pool.map(part, range(nchunk)))
            G = np.sum([p[1] for p in parts], axis=0)
            H = np.sum([p[2] for p in parts], axis=0)
            cnt = np.sum([p[3] for p in parts], axis=0)
            value = -learning_rate * G / (H + lambda_l2)
            GN, HN = reference.subtree_sums(tree, G), reference.subtree_sums(tree, H)
            gain = np.zeros(L - 1)
            for node in range(L - 1):
                lc, rc = int(tree["left_child"][node]), int(tree["right_child"][node])
                gl, hl = reference.child_sum(lc, G, GN), reference.child_sum(lc, H, HN)
                gr, hr = reference.child_sum(rc, G, GN), reference.child_sum(rc, H, HN)
                gain[node] = (gl * gl / (hl + lambda_l2) + gr * gr / (hr + lambda_l2)
                              - GN[node] ** 2 / (HN[node] + lambda_l2))
            for c, p in enumerate(parts):
                score[edges[c]:edges[c + 1]] += value[p[0]]
            out["leaf_value"].append(value)
            out["leaf_count"].append(cnt)
            out["node_count"].append(reference.subtree_sums(tree, cnt))
            out["gain"].append(gain)
            out["sample_score"].append(score[sample].copy())
    return out
