"""GOSS: Gradient-based One-Side Sampling (reference: src/boosting/goss.hpp;
Ke et al., NeurIPS 2017, Algorithm 2).

A tree is grown on the ``top_k = int(N * top_rate)`` rows with the largest
sum-over-classes ``|grad * hess|`` (goss.hpp:88-98; ties go to the lower row
number) plus a uniform draw of the rest at rate ``other_k / (N - top_k)``,
``other_k = int(N * other_rate)``, whose gradients and hessians are
multiplied by ``(N - top_k) / other_k`` (goss.hpp:100-126). Sampling starts
after ``1 / learning_rate`` iterations (goss.hpp:134-137).

The sample is the histogram's ROW SET: the step hands the grower the 0/1
mask as ``included`` with ``sampled=True`` and every histogram pass, the
root's too, runs over the included rows only (grower.grow_tree), so a tree
costs about the included share of a full one. Routing still moves every
row, and every row is scored; the tree's leaf values are taken from the
included rows' own sums (grower._leaf_values_from_rows).

Selection makes no index array: the ``top_k``-th largest weight is found by
bisection on the weights' bit patterns (non-negative float32 compares as its
int32 pattern: 31 compare-and-count passes over the rows, no sort), rows
above it are top rows, and rows that tie with it are taken in row order
until ``top_k`` is full (one running count).

Departure from the reference, kept and written down: the other set is a
Bernoulli draw (its size is binomial around ``other_k``), where goss.hpp
draws rows one after another with the probability adjusted so that exactly
``other_k`` are taken. Under ``tree_learner=data`` each shard samples its
own rows with its own counts, as the reference's workers do.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import observability as obs
from ..config import Config
from ..utils.log import Log
from .gbdt import GBDT, SampleStats


class GossSample(NamedTuple):
    is_top: jnp.ndarray      # bool [N]
    is_other: jnp.ndarray    # bool [N] drawn from the rest
    scale: jnp.ndarray       # f32 [N] (n - top_k) / other_k on the drawn
                             # rows, 1 elsewhere


def kth_largest_bits(bits: jnp.ndarray, k) -> jnp.ndarray:
    """The ``k``-th largest of ``bits`` (int32, the patterns of non-negative
    float32 values; -1 marks a row that is not there): the largest ``t``
    with ``count(bits >= t) >= k``. Bisection over the 31 bits, one
    compare-and-count over the rows a step."""
    def step(_, lo_hi):
        lo, hi = lo_hi
        mid = lo + (hi - lo) // 2 + ((hi - lo) & 1)      # the upper middle
        enough = jnp.sum((bits >= mid).astype(jnp.int32)) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    # NaN weights (patterns past infinity) rank as the largest
    lo, _ = jax.lax.fori_loop(
        0, 31, step, (jnp.asarray(0, jnp.int32),
                      jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32)))
    return lo


def goss_select(weights: jnp.ndarray, valid: jnp.ndarray,
                uniform: jnp.ndarray, n, top_k, other_k) -> GossSample:
    """One table's (or one shard's) sample. ``weights`` f32 [N] >= 0,
    ``valid`` bool [N] (padding off), ``uniform`` f32 [N] in [0, 1);
    ``n`` real rows, ``top_k``, ``other_k``: Python ints, or traced int32
    scalars where a shard's own counts are only known on the device."""
    bits = jnp.where(valid, jax.lax.bitcast_convert_type(weights, jnp.int32), -1)
    t = kth_largest_bits(bits, top_k)
    above = bits > t
    ties = bits == t
    # exactly top_k rows: ties with the threshold in row order
    room = top_k - jnp.sum(above.astype(jnp.int32))
    is_top = above | (ties & (jnp.cumsum(ties.astype(jnp.int32)) <= room))
    if isinstance(n, int):
        prob, amplify = other_k / max(n - top_k, 1), (n - top_k) / other_k
    else:
        rest = (n - top_k).astype(jnp.float32)
        prob = other_k.astype(jnp.float32) / jnp.maximum(rest, 1.0)
        amplify = rest / other_k.astype(jnp.float32)
    is_other = valid & ~is_top & (uniform < prob)
    return GossSample(is_top, is_other, jnp.where(is_other, amplify, 1.0))


class GOSS(GBDT):
    # conservative: the sampling warm-up boundary (1/learning_rate) and its
    # interaction with fused batches is unvalidated — GBDT.__init__ falls
    # back to tree_batch=1 with a warning
    supports_tree_batch = False
    # every step carries the sample into the grower as its row set
    samples_rows = True

    def __init__(self, config: Config, train_set, objective=None):
        super().__init__(config, train_set, objective)
        if config.bagging_freq > 0 and config.bagging_fraction != 1.0:
            Log.fatal("Cannot use bagging in GOSS")
        Log.info("Using GOSS")
        self.bagging_on = False
        n, top_k, other_k = self._goss_counts(self.num_data)
        obs.get_registry().gauge("sample.amplify").set((n - top_k) / other_k)

    def _goss_counts(self, n):
        """(n, top_k, other_k) of ``n`` rows: Python ints for an int,
        int32 scalars for a traced count."""
        cfg = self.config
        if isinstance(n, int):
            return (n, max(1, int(n * cfg.top_rate)),
                    max(1, int(n * cfg.other_rate)))
        nf = n.astype(jnp.float32)
        return (n, jnp.maximum(1, (nf * cfg.top_rate).astype(jnp.int32)),
                jnp.maximum(1, (nf * cfg.other_rate).astype(jnp.int32)))

    def _select(self, weights, pad_mask, uniform) -> GossSample:
        """The sample of the whole table, or under a row-sharded mesh of
        each shard's own rows by its own counts (no row crosses devices)."""
        rows = self.pctx.row_sharding()
        if rows is None:
            return goss_select(weights, pad_mask > 0, uniform,
                               *self._goss_counts(self.num_data))

        def shard(w, pm, u):
            n = jnp.sum((pm > 0).astype(jnp.int32))
            return goss_select(w, pm > 0, u, *self._goss_counts(n))

        return jax.shard_map(shard, mesh=self.pctx.mesh,
                             in_specs=(rows.spec,) * 3,
                             out_specs=GossSample(*(rows.spec,) * 3),
                             check_vma=False)(weights, pad_mask, uniform)

    def _sampling(self, g, h, bag_mask, key, it):
        warmup = int(1.0 / self.config.learning_rate)
        weights = jnp.sum(jnp.abs(g * h), axis=0)                   # [Npad]
        s = self._select(weights, self.pad_mask,
                         jax.random.uniform(key, weights.shape))
        use_goss = it >= warmup
        is_top, is_other = use_goss & s.is_top, use_goss & s.is_other
        mask = jnp.where(use_goss, (is_top | is_other).astype(jnp.float32),
                         self.pad_mask)
        scale = jnp.where(use_goss, s.scale, 1.0)[None, :]
        return mask, g * scale, h * scale, SampleStats.count(is_top, is_other, mask)
