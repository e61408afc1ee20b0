"""DART: Dropouts meet Multiple Additive Regression Trees
(reference: src/boosting/dart.hpp).

Per iteration: select a drop set among previous trees (weighted or uniform,
dart.hpp:85-112), subtract their contribution from the training/validation
scores, train the new tree with shrinkage lr/(1+k) (xgboost mode: lr/(lr+k)),
then renormalize the dropped trees by k/(k+1) (xgboost mode: k/(k+lr))
(dart.hpp:133-180). Dropped-tree contributions are recomputed by binned
traversal (ops/predict.py) — the TPU analog of ScoreUpdater::AddScore on a
negatively-shrunk tree.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as obs
from ..config import Config
from ..ops.predict import leaves_from_binned
from ..utils.log import Log
from .gbdt import GBDT


class DART(GBDT):
    # host-side per-iteration drop-set selection + score renormalization
    # cannot fuse into a device-resident scan — GBDT.__init__ falls back to
    # tree_batch=1 with a warning
    supports_tree_batch = False
    # the drop-set replay reads the RESIDENT code matrix per tree
    # (_contrib_fn over self.Xb) — out-of-core streaming has no such array
    supports_stream = False

    def __init__(self, config: Config, train_set, objective=None):
        super().__init__(config, train_set, objective)
        Log.info("Using DART")
        if config.nan_policy in ("raise", "skip_iter"):
            # the gated no-op step composes with DART's host-side drop/
            # renormalize arithmetic incorrectly (the post-step correction
            # would re-add dropped contributions a skipped step never took
            # out) — only the in-step policies are sound here
            Log.fatal("nan_policy=%s is not supported with boosting=dart "
                      "(use none or clip)", config.nan_policy)
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0
        self._drop_rng = np.random.default_rng(config.drop_seed)
        # train matrix may be EFB-bundled; valid matrices never are.
        # Feature metadata travels as jit arguments (multi-host forbids
        # closing over arrays spanning non-addressable devices).
        self._contrib_fn = jax.jit(
            lambda tree, Xb, nb, mc, db: self._tree_contrib(
                tree, Xb, nb, mc, db, self.bundle))
        self._contrib_fn_valid = jax.jit(
            lambda tree, Xb, nb, mc, db: self._tree_contrib(
                tree, Xb, nb, mc, db, None))

    def _tree_contrib(self, tree, Xb, num_bins, missing_code, default_bin,
                      bundle):
        leaves = leaves_from_binned(tree, Xb, num_bins, missing_code,
                                    default_bin, bundle=bundle)
        return tree.leaf_value[leaves]

    def _select_drop(self) -> List[int]:
        cfg = self.config
        n = self.iter_
        if n == 0 or self._drop_rng.random() < cfg.skip_drop:
            return []
        drop = []
        if not cfg.uniform_drop:
            inv_avg = len(self.tree_weight) / self.sum_weight if self.sum_weight > 0 else 0.0
            rate = cfg.drop_rate
            if cfg.max_drop > 0 and self.sum_weight > 0:
                rate = min(rate, cfg.max_drop * inv_avg / self.sum_weight)
            for i in range(n):
                if self._drop_rng.random() < rate * self.tree_weight[i] * inv_avg:
                    drop.append(i)
        else:
            rate = cfg.drop_rate
            if cfg.max_drop > 0:
                rate = min(rate, cfg.max_drop / max(n, 1))
            for i in range(n):
                if self._drop_rng.random() < rate:
                    drop.append(i)
        return drop

    # the drop-set arithmetic is inside step.host_s and in none of its parts
    @obs.step_call()
    def train_one_iter(self) -> None:
        cfg = self.config
        lr = cfg.learning_rate
        drop = self._select_drop()
        k = len(drop)
        if cfg.xgboost_dart_mode:
            shrinkage = lr if k == 0 else lr / (lr + k)
            factor = k / (k + lr) if k else 0.0
        else:
            shrinkage = lr / (1.0 + k)
            factor = k / (k + 1.0) if k else 0.0

        K = self.num_models
        if k:
            drop_train = jnp.zeros_like(self.score)
            drop_valid = [jnp.zeros_like(vs.score) for vs in self.valid_sets]
            nb, mc, db = self.num_bins, self.missing_code, self.default_bin
            for i in drop:
                for c in range(K):
                    tree = self.models[i][c]
                    drop_train = drop_train.at[c].add(
                        self._contrib_fn(tree, self.Xb, nb, mc, db))
                    for vi, vs in enumerate(self.valid_sets):
                        drop_valid[vi] = drop_valid[vi].at[c].add(
                            self._contrib_fn_valid(tree, vs.Xb, nb, mc, db))
            score_adj = self.score - drop_train
            for vi, vs in enumerate(self.valid_sets):
                vs.score = vs.score - drop_valid[vi]
        else:
            score_adj = self.score

        score, out_valid = self._run_step(score_adj, shrinkage)
        if k:
            score = score + drop_train * factor
        self.score = score
        for vi, vs in enumerate(self.valid_sets):
            new_v = jnp.stack(out_valid[vi])
            vs.score = new_v + drop_valid[vi] * factor if k else new_v

        # permanently renormalize the dropped trees (dart.hpp:138-158)
        for i in drop:
            for c in range(K):
                t = self.models[i][c]
                self.models[i][c] = t._replace(leaf_value=t.leaf_value * factor)
            if not cfg.uniform_drop:
                if cfg.xgboost_dart_mode:
                    self.sum_weight -= self.tree_weight[i] * (1.0 / (k + lr))
                else:
                    self.sum_weight -= self.tree_weight[i] * (1.0 / (k + 1.0))
                self.tree_weight[i] *= factor
        self.tree_weight.append(shrinkage)
        self.sum_weight += shrinkage
