"""Plain reference for a histogram GBDT's first trees: NumPy, float64, no
binning, no histograms, no device. It imports nothing of the program and
takes nothing the program made except the thing under test: the trees.

What "the same semantics" means here. A leaf-wise GBDT decides, per tree,
(a) where each row goes, (b) what each leaf outputs, (c) what each split
gained. Given raw feature values, labels and a tree's split structure
(feature, real threshold, children) the answers to (a)-(c) are fixed by the
objective's arithmetic and do not depend on how histograms were built:

    p = sigmoid(score); g = p - y; h = p * (1 - p)          (binary logloss)
    leaf value  = -lr * G / (H + lambda_l2),  G, H = sums of g, h over the leaf
    split gain  = GL^2/(HL+l2) + GR^2/(HR+l2) - GP^2/(HP+l2)
    row goes left  iff  x[feature] <= threshold

`follow` walks the first trees in order: it routes every row by raw value,
sums g and h per leaf in float64, derives its OWN leaf values and scores and
carries those to the next tree, so an error the program makes in one tree is
not inherited by the reference's next one. A tree from the middle of a run
(the steady tree, dispatched after the window) is valued the same way from
the program's resident score before its dispatch (``restart``): that score is
the step's input, as the rows are. Work is split over row chunks (threads;
each chunk transposes the columns the trees use, so gathers read contiguous
memory), and the per-chunk partial sums are added in chunk order.

The split finder's choice is checked where an exact scan is affordable: at a
few internal nodes per tree, drawn from the seed among those that hold
``scan["rows"]`` = [least, most] rows, ALL rows under the node are sorted by
every feature and every threshold between two distinct values is tried with
the reference's own g and h (`node_split_loss`), and at the roots the same on
the sample rows (`root_split_loss`).

``precision`` lowers the arithmetic the way a tempting optimisation would:
"bf16" rounds every g and h to bfloat16 before it is summed (what a
histogram kernel that dropped its low half does). That is the control.
``rows_kept`` < 1 leaves the tail of every chunk out of the sums and takes
leaf values from the rest (the "half of the batch" fault).
"""
import concurrent.futures

import numpy as np

THREADS = 12
K_ZERO_RANGE = 1e-35      # |x| <= this is "zero" for missing_type zero


def _round_bf16(a: np.ndarray) -> np.ndarray:
    """float -> nearest-even bfloat16 -> float64."""
    u = a.astype(np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def go_left(tree: dict, node: int, v: np.ndarray) -> np.ndarray:
    """The numerical decision of node ``node`` on raw values ``v``."""
    dt = int(tree["decision_type"][node])
    if dt & 1:
        raise ValueError("categorical split: not in this reference")
    missing = (dt >> 2) & 3               # 0 none, 1 zero, 2 nan
    left = v <= tree["threshold"][node]
    if missing == 1:
        left = np.where(np.abs(v) <= K_ZERO_RANGE, bool(dt & 2), left)
    elif missing == 2:
        left = np.where(np.isnan(v), bool(dt & 2), left)
    return left


def route(cols: dict, n: int, tree: dict) -> np.ndarray:
    """Leaf index of each of ``n`` rows; ``cols[f]`` is feature f's
    contiguous column. Rows are partitioned node by node, keeping order."""
    leaf = np.zeros(n, np.int32)
    if int(tree["num_leaves"]) <= 1:
        return leaf
    stack = [(0, None)]
    while stack:
        node, idx = stack.pop()
        col = cols[int(tree["split_feature"][node])]
        left = go_left(tree, node, col if idx is None else col[idx])
        for child, m in ((int(tree["left_child"][node]), left),
                         (int(tree["right_child"][node]), ~left)):
            sub = np.flatnonzero(m) if idx is None else idx[m]
            if child < 0:
                leaf[sub] = ~child
            elif len(sub):
                stack.append((child, sub))
    return leaf


def used_features(trees) -> list:
    used = set()
    for t in trees:
        used.update(int(f) for f in t["split_feature"][: int(t["num_leaves"]) - 1])
    return sorted(used)


def _columns(X: np.ndarray, lo: int, hi: int, feats) -> dict:
    block = np.ascontiguousarray(X[lo:hi][:, feats].T)
    return {f: block[i] for i, f in enumerate(feats)}


def subtree_sums(tree: dict, per_leaf: np.ndarray) -> np.ndarray:
    """Per internal node, the sum of ``per_leaf`` over the leaves below it."""
    m = int(tree["num_leaves"]) - 1
    out = np.zeros(m, per_leaf.dtype)
    # children are created after their parents, so a reverse sweep sees
    # every child before its parent
    for node in range(m - 1, -1, -1):
        for child in (int(tree["left_child"][node]), int(tree["right_child"][node])):
            out[node] += per_leaf[~child] if child < 0 else out[child]
    return out


def child_sum(child: int, per_leaf, per_node):
    return per_leaf[~child] if child < 0 else per_node[child]


def sigmoid(s):
    return 1.0 / (1.0 + np.exp(-s))


def logloss(score, y) -> float:
    # log(1 + exp(-m)) with m = score for y=1, -score for y=0
    m = np.where(y > 0, score, -score)
    return float(np.mean(np.logaddexp(0.0, -m)))


def leaves_under(tree: dict, node: int) -> np.ndarray:
    """Mask over the leaves: those below internal node ``node``."""
    under = np.zeros(int(tree["num_leaves"]), bool)
    stack = [node]
    while stack:
        at = stack.pop()
        for child in (int(tree["left_child"][at]), int(tree["right_child"][at])):
            if child < 0:
                under[~child] = True
            else:
                stack.append(child)
    return under


def follow(X: np.ndarray, y: np.ndarray, trees, *, learning_rate: float,
           lambda_l2: float, init_score: float, sample: np.ndarray,
           precision: str = "f64", rows_kept: float = 1.0, restart: dict = None,
           scan: dict = None, threads: int = THREADS) -> dict:
    """Follow ``trees`` (their structure only) over all rows of ``X``.

    ``restart`` {index of a tree: score of every row}: that tree is valued
    from the given score and not from the reference's own running one.
    ``scan`` {"nodes", "rows": [least, most], "min_side", "seed"}: per tree,
    exact-scan that many internal nodes (see the head of this file).

    Returns per tree the reference's leaf values, leaf and node counts and
    split gains, at the ``sample`` rows its scores after each tree, and with
    ``scan`` per tree [worst node's split loss, least runner-up feature's
    loss, nodes scanned]."""
    n = X.shape[0]
    feats = used_features(trees)
    nchunk = max(1, min(threads, n // 4096))
    bounds = np.linspace(0, n, nchunk + 1).astype(np.int64)
    score = np.full(n, float(init_score))
    out = {"leaf_value": [], "leaf_count": [], "node_count": [], "gain": [],
           "sample_score": [], "node_scan": []}
    pick = np.random.default_rng([int(scan["seed"]), 2]) if scan else None
    with concurrent.futures.ThreadPoolExecutor(nchunk) as pool:
        cols = list(pool.map(
            lambda c: _columns(X, bounds[c], bounds[c + 1], feats), range(nchunk)))
        for t, tree in enumerate(trees):
            L = int(tree["num_leaves"])
            if restart and t in restart:
                score = np.asarray(restart[t], np.float64).copy()

            def part(c):
                lo, hi = bounds[c], bounds[c + 1]
                leaf = route(cols[c], hi - lo, tree)
                p = sigmoid(score[lo:hi])
                g = p - y[lo:hi]
                h = p * (1.0 - p)
                if precision == "bf16":
                    g, h = _round_bf16(g), _round_bf16(h)
                elif precision != "f64":
                    raise ValueError(f"unknown precision {precision!r}")
                keep = slice(0, max(1, int(round((hi - lo) * rows_kept))))
                return (leaf,
                        np.bincount(leaf[keep], weights=g[keep], minlength=L),
                        np.bincount(leaf[keep], weights=h[keep], minlength=L),
                        np.bincount(leaf, minlength=L), g, h)

            parts = list(pool.map(part, range(nchunk)))
            G = np.sum([p[1] for p in parts], axis=0)
            H = np.sum([p[2] for p in parts], axis=0)
            cnt = np.sum([p[3] for p in parts], axis=0)
            value = -learning_rate * G / (H + lambda_l2)
            GN, HN = subtree_sums(tree, G), subtree_sums(tree, H)
            gain = np.zeros(L - 1)
            for node in range(L - 1):
                lc, rc = int(tree["left_child"][node]), int(tree["right_child"][node])
                gl, hl = child_sum(lc, G, GN), child_sum(lc, H, HN)
                gr, hr = child_sum(rc, G, GN), child_sum(rc, H, HN)
                gain[node] = (gl * gl / (hl + lambda_l2) + gr * gr / (hr + lambda_l2)
                              - GN[node] ** 2 / (HN[node] + lambda_l2))
            for c, p in enumerate(parts):
                score[bounds[c]:bounds[c + 1]] += value[p[0]]
            node_cnt = subtree_sums(tree, cnt)
            if scan:
                out["node_scan"].append(_scan_nodes(
                    X, tree, parts, bounds, node_cnt, gain, lambda_l2, scan, pick, pool))
            del parts
            out["leaf_value"].append(value)
            out["leaf_count"].append(cnt)
            out["node_count"].append(node_cnt)
            out["gain"].append(gain)
            out["sample_score"].append(score[sample].copy())
    return out


def walk(X_rows: np.ndarray, trees, init_score: float) -> np.ndarray:
    """Raw score of each row under ``trees`` as they stand: their own leaf
    values, routed by raw value. Used on a sample of rows for every tree the
    run produced (the forest walk and the resident training score)."""
    n = X_rows.shape[0]
    score = np.full(n, float(init_score))
    cols = _columns(X_rows, 0, n, used_features(trees))
    for tree in trees:
        score += np.asarray(tree["leaf_value"], np.float64)[route(cols, n, tree)]
    return score


def _best_gain_of_feature(col, g, h, lambda_l2, min_side):
    """Best gain of any threshold between two distinct values of ``col``
    that leaves at least ``min_side`` rows on each side: an exact scan."""
    o = np.argsort(col, kind="stable")
    v, G, H = col[o], np.cumsum(g[o]), np.cumsum(h[o])
    n = len(v)
    left = np.arange(1, n)                       # rows on the left of cut i
    ok = (v[1:] != v[:-1]) & (left >= min_side) & (n - left >= min_side)
    if not ok.any():
        return 0.0
    gl, hl = G[:-1][ok], H[:-1][ok]
    gain = (gl * gl / (hl + lambda_l2) + (G[-1] - gl) ** 2 / (H[-1] - hl + lambda_l2)
            - G[-1] ** 2 / (H[-1] + lambda_l2))
    return float(gain.max())


def _scan_nodes(X, tree, parts, bounds, node_cnt, gain, lambda_l2, scan, pick, pool):
    """[worst split loss, least runner-up loss, nodes scanned] over
    ``scan["nodes"]`` internal nodes of ``tree`` drawn by ``pick`` among those
    with scan["rows"][0] <= rows <= scan["rows"][1]. A node's split loss is
    1 - gain(its own split) / gain(best split of any feature at any threshold
    that leaves ``min_side`` rows on each side), both exact over ALL rows
    under the node with the reference's g and h: 0 for a finder that saw
    every threshold, a little more for one that sees bin boundaries."""
    least, most = scan["rows"]
    eligible = np.flatnonzero((node_cnt >= least) & (node_cnt <= most))
    worst, runner_up, scanned = 0.0, np.inf, 0
    for node in pick.permutation(eligible)[: int(scan["nodes"])]:
        under = leaves_under(tree, int(node))
        idx, g, h = [], [], []
        for c, p in enumerate(parts):
            local = np.flatnonzero(under[p[0]])
            idx.append(local + bounds[c])
            g.append(p[4][local])
            h.append(p[5][local])
        rows, g, h = X[np.concatenate(idx)], np.concatenate(g), np.concatenate(h)
        ranked = sorted(pool.map(
            lambda f: _best_gain_of_feature(np.ascontiguousarray(rows[:, f]), g, h,
                                            lambda_l2, int(scan["min_side"])),
            range(rows.shape[1])), reverse=True)
        best = max(ranked[0], float(gain[node]))
        worst = max(worst, 1.0 - float(gain[node]) / best)
        runner_up = min(runner_up, 1.0 - ranked[1] / best)
        scanned += 1
    return [worst, float(runner_up), scanned]


def root_split_loss(X_rows, y_rows, trees, scores_before, lambda_l2,
                    threads: int = THREADS):
    """How much of the best root split the trees' root splits give away, on
    these rows: per tree 1 - gain(the tree's root split) / gain(best split of
    any feature at any threshold), both taken on ``X_rows`` with the
    reference's gradients at ``scores_before[t]``; never below 0. Returns
    (worst tree's loss, the least loss a finder would read that took the
    runner-up FEATURE's best split instead)."""
    n, F = X_rows.shape
    min_side = max(1, n // 100)
    worst, runner_up = 0.0, np.inf
    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        for tree, score in zip(trees, scores_before):
            p = sigmoid(np.asarray(score, np.float64))
            g, h = p - y_rows, p * (1.0 - p)
            per_feature = list(pool.map(
                lambda f: _best_gain_of_feature(
                    np.ascontiguousarray(X_rows[:, f]), g, h, lambda_l2, min_side),
                range(F)))
            left = go_left(tree, 0, X_rows[:, int(tree["split_feature"][0])])
            gl, hl, gt, ht = g[left].sum(), h[left].sum(), g.sum(), h.sum()
            mine = (gl * gl / (hl + lambda_l2) + (gt - gl) ** 2 / (ht - hl + lambda_l2)
                    - gt * gt / (ht + lambda_l2))
            ranked = sorted(per_feature, reverse=True)
            best = max(ranked[0], mine)
            worst = max(worst, 1.0 - mine / best)
            runner_up = min(runner_up, 1.0 - ranked[1] / best)
    return worst, float(runner_up)
