"""Objective functions: score -> (gradient, hessian), as JAX-traceable math.

Reference: src/objective/*.hpp + factory objective_function.cpp:11-33. Each
objective exposes:
- `gradients(score[K,N], label[N], weight[N]|None) -> (g[K,N], h[K,N])`,
  traced into the boosting-iteration jit (the reference's GetGradients OMP
  loops become fused elementwise XLA; lambdarank's per-query pairwise loops
  become padded-bucket batched matrices),
- `convert_output(raw)` — sigmoid/softmax/exp transform (objective_function.h),
- host-side `init(...)` for label checks / class counts / query structure,
- `boost_from_average_score()` (gbdt.cpp:357-377 + GetCustomAverage).

Scores are laid out [num_models, num_data] — the reference's k*num_data+i
flattening (multiclass_objective.hpp:60-75) as a 2-D array.
"""
from __future__ import annotations

import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .dataset import Metadata
from .utils.log import Log


def _apply_weight(g, h, weight):
    if weight is None:
        return g, h
    return g * weight, h * weight


class Objective:
    """Base objective (reference: include/LightGBM/objective_function.h)."""

    name = "custom"
    num_models = 1
    is_constant_hessian = False

    # device arrays ``gradients`` reads besides its arguments; the training
    # step takes them as an argument (GBDT._step_consts), never as constants
    device_arrays = None

    def __init__(self, config: Config):
        self.config = config

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.num_data = num_data

    def device_bytes(self) -> int:
        """HBM the objective adds to a training step (the pre-flight's
        ``objective`` term): none for an elementwise objective."""
        return 0

    def gradients(self, score: jnp.ndarray, label: jnp.ndarray,
                  weight: Optional[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def convert_output(self, raw: jnp.ndarray) -> jnp.ndarray:
        return raw

    def boost_from_average_score(self) -> Optional[float]:
        """Init score when boost_from_average applies; None otherwise."""
        return None

    def _weighted_label_mean(self, metadata: Metadata) -> float:
        label = metadata.label.astype(np.float64)
        if metadata.weight is not None:
            w = metadata.weight.astype(np.float64)
            return float((label * w).sum() / w.sum())
        return float(label.mean())


class RegressionL2(Objective):
    """regression / l2 / mse (regression_objective.hpp:13-75)."""
    name = "regression"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self.is_constant_hessian = metadata.weight is None
        self._avg = self._weighted_label_mean(metadata)

    def gradients(self, score, label, weight):
        g = score - label[None, :]
        h = jnp.ones_like(g)
        return _apply_weight(g, h, weight)

    def boost_from_average_score(self):
        return self._avg


def _gaussian_hessian(score, label, grad, eta, weight=None):
    """ApproximateHessianWithGaussian (utils/common.h:486-495)."""
    w = 1.0 if weight is None else weight
    diff = score - label
    x = jnp.abs(diff)
    a = 2.0 * jnp.abs(grad) * w
    c = jnp.maximum((jnp.abs(score) + jnp.abs(label)) * eta, 1.0e-10)
    return w * jnp.exp(-x * x / (2.0 * c * c)) * a / (c * jnp.sqrt(2.0 * jnp.pi))


class RegressionL1(Objective):
    """regression_l1 / mae (regression_objective.hpp:80-147)."""
    name = "regression_l1"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self._avg = self._weighted_label_mean(metadata)

    def gradients(self, score, label, weight):
        label = label[None, :]
        diff = score - label
        sign = jnp.where(diff >= 0.0, 1.0, -1.0)
        if weight is not None:
            g = sign * weight
            h = _gaussian_hessian(score, label, g, self.config.gaussian_eta, weight)
        else:
            g = sign
            h = _gaussian_hessian(score, label, g, self.config.gaussian_eta)
        return g, h

    def boost_from_average_score(self):
        return self._avg


class RegressionHuber(Objective):
    """huber (regression_objective.hpp:151-233)."""
    name = "huber"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self._avg = self._weighted_label_mean(metadata)

    def gradients(self, score, label, weight):
        label = label[None, :]
        delta = self.config.huber_delta
        diff = score - label
        inner = jnp.abs(diff) <= delta
        g_out = jnp.where(diff >= 0.0, delta, -delta)
        if weight is not None:
            g = jnp.where(inner, diff * weight, g_out * weight)
            h = jnp.where(inner, weight,
                          _gaussian_hessian(score, label, g_out * weight,
                                            self.config.gaussian_eta, weight))
        else:
            g = jnp.where(inner, diff, g_out)
            h = jnp.where(inner, 1.0,
                          _gaussian_hessian(score, label, g_out, self.config.gaussian_eta))
        return g, h

    def boost_from_average_score(self):
        return self._avg


class RegressionFair(Objective):
    """fair (regression_objective.hpp:237-297)."""
    name = "fair"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self._avg = self._weighted_label_mean(metadata)

    def gradients(self, score, label, weight):
        c = self.config.fair_c
        x = score - label[None, :]
        g = c * x / (jnp.abs(x) + c)
        h = c * c / (jnp.abs(x) + c) ** 2
        return _apply_weight(g, h, weight)

    def boost_from_average_score(self):
        return self._avg


class RegressionPoisson(Objective):
    """poisson (regression_objective.hpp:301-399): internal score is log-rate."""
    name = "poisson"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label = metadata.label
        if label.min() < 0.0:
            Log.fatal("[poisson]: at least one target label is negative.")
        if label.sum() == 0.0:
            Log.fatal("[poisson]: sum of labels is zero.")
        self._init_score = math.log(self._weighted_label_mean(metadata))

    def gradients(self, score, label, weight):
        ef = jnp.exp(score)
        g = ef - label[None, :]
        h = ef
        return _apply_weight(g, h, weight)

    def convert_output(self, raw):
        return jnp.exp(raw)

    def boost_from_average_score(self):
        return self._init_score


class BinaryLogloss(Objective):
    """binary (binary_objective.hpp:13-180)."""
    name = "binary"

    def __init__(self, config: Config, positive_class: Optional[int] = None):
        super().__init__(config)
        if config.sigmoid <= 0.0:
            Log.fatal("Sigmoid parameter %f should be greater than zero", config.sigmoid)
        if config.is_unbalance and abs(config.scale_pos_weight - 1.0) > 1e-6:
            Log.fatal("Cannot set is_unbalance and scale_pos_weight at the same time.")
        self.positive_class = positive_class  # for OVA sub-objectives

    def _is_pos(self, label: np.ndarray) -> np.ndarray:
        if self.positive_class is not None:
            return label.astype(np.int32) == self.positive_class
        return label > 0

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        pos = self._is_pos(metadata.label)
        cnt_pos = int(pos.sum())
        cnt_neg = num_data - cnt_pos
        self.need_train = True
        if cnt_pos == 0 or cnt_neg == 0:
            Log.warning("Only contain one class.")
            self.need_train = False
        Log.info("Number of positive: %d, number of negative: %d", cnt_pos, cnt_neg)
        w_neg, w_pos = 1.0, 1.0
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.config.scale_pos_weight
        self.label_weights = (w_neg, w_pos)

    def gradients(self, score, label, weight):
        sig = self.config.sigmoid
        if self.positive_class is not None:
            is_pos = label.astype(jnp.int32) == self.positive_class
        else:
            is_pos = label > 0
        y = jnp.where(is_pos, 1.0, -1.0)
        lw = jnp.where(is_pos, self.label_weights[1], self.label_weights[0])
        response = -y * sig / (1.0 + jnp.exp(y * sig * score))
        abs_resp = jnp.abs(response)
        g = response * lw
        h = abs_resp * (sig - abs_resp) * lw
        if not self.need_train:
            g = jnp.zeros_like(g)
            h = jnp.zeros_like(h)
        return _apply_weight(g, h, weight)

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.config.sigmoid * raw))


class MulticlassSoftmax(Objective):
    """multiclass softmax (multiclass_objective.hpp:16-140)."""
    name = "multiclass"

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_models = config.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        li = metadata.label.astype(np.int64)
        if li.min() < 0 or li.max() >= self.num_models:
            Log.fatal("Label must be in [0, %d), but found %d in label",
                      self.num_models, int(li.min() if li.min() < 0 else li.max()))

    def gradients(self, score, label, weight):
        p = jax.nn.softmax(score, axis=0)                 # [K, N]
        onehot = (label.astype(jnp.int32)[None, :]
                  == jnp.arange(self.num_models, dtype=jnp.int32)[:, None])
        g = p - onehot.astype(p.dtype)
        h = 2.0 * p * (1.0 - p)
        return _apply_weight(g, h, weight)

    def convert_output(self, raw):
        return jax.nn.softmax(raw, axis=0)


class MulticlassOVA(Objective):
    """multiclassova (multiclass_objective.hpp:139+): K independent binary."""
    name = "multiclassova"

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_models = config.num_class
        self.subs = [BinaryLogloss(config, positive_class=k)
                     for k in range(self.num_models)]

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        for sub in self.subs:
            sub.init(metadata, num_data)

    def gradients(self, score, label, weight):
        gs, hs = [], []
        for k, sub in enumerate(self.subs):
            g, h = sub.gradients(score[k:k + 1], label, weight)
            gs.append(g)
            hs.append(h)
        return jnp.concatenate(gs, axis=0), jnp.concatenate(hs, axis=0)

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.config.sigmoid * raw))


class CrossEntropy(Objective):
    """xentropy (xentropy_objective.hpp:39-137): labels in [0,1]."""
    name = "xentropy"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label = metadata.label
        if label.min() < 0.0 or label.max() > 1.0:
            Log.fatal("[xentropy]: label must be in [0, 1]")
        if metadata.weight is not None:
            if metadata.weight.min() < 0.0:
                Log.fatal("[xentropy]: at least one weight is negative.")
            if metadata.weight.sum() == 0.0:
                Log.fatal("[xentropy]: sum of weights is zero.")
        pavg = min(max(self._weighted_label_mean(metadata), 1e-15), 1.0 - 1e-15)
        self._init_score = math.log(pavg / (1.0 - pavg))

    def gradients(self, score, label, weight):
        z = jax.nn.sigmoid(score)
        g = z - label[None, :]
        h = z * (1.0 - z)
        return _apply_weight(g, h, weight)

    def convert_output(self, raw):
        return jax.nn.sigmoid(raw)

    def boost_from_average_score(self):
        return self._init_score


class CrossEntropyLambda(Objective):
    """xentlambda (xentropy_objective.hpp:143-260)."""
    name = "xentlambda"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label = metadata.label
        if label.min() < 0.0 or label.max() > 1.0:
            Log.fatal("[xentlambda]: label must be in [0, 1]")
        if metadata.weight is not None and metadata.weight.min() <= 0.0:
            Log.fatal("[xentlambda]: at least one weight is non-positive.")
        sumy = float(label.astype(np.float64).sum())
        havg = sumy / num_data
        self._init_score = math.log(max(math.expm1(havg), 1e-15))

    def gradients(self, score, label, weight):
        label = label[None, :]
        if weight is None:
            z = jax.nn.sigmoid(score)
            return z - label, z * (1.0 - z)
        w = weight
        epf = jnp.exp(score)
        hhat = jnp.log1p(epf)
        z = 1.0 - jnp.exp(-w * hhat)
        enf = jnp.exp(-score)
        g = (1.0 - label / z) * w / (1.0 + enf)
        c = 1.0 / (1.0 - z)
        d = 1.0 + epf
        a = w * epf / (d * d)
        d2 = c - 1.0
        b = (c / (d2 * d2)) * (1.0 + w * epf - c)
        h = a * (1.0 + label * b)
        return g, h

    def convert_output(self, raw):
        return jnp.log1p(jnp.exp(raw))

    def boost_from_average_score(self):
        return self._init_score


# ---------------------------------------------------------------------------
# lambdarank
# ---------------------------------------------------------------------------

DEFAULT_LABEL_GAIN_SIZE = 31


def default_label_gain() -> List[float]:
    """2^i - 1 (reference: config.cpp label_gain default)."""
    return [float((1 << i) - 1) for i in range(DEFAULT_LABEL_GAIN_SIZE)]


class LambdarankNDCG(Objective):
    """lambdarank (rank_objective.hpp:19-208).

    TPU formulation: each query is padded to a power-of-two length class
    (8, 16, ..., the first at or above the longest query) and a class runs
    as ``[chunk, M, M]`` pair blocks under ``lax.map`` (scope
    ``rank.pairwise``; ``chunk`` = queries a block, ``QUERY_CHUNK_BUDGET``
    pair slots or one query): the reference's per-query double loop
    (rank_objective.hpp:113-160) with the sigmoid lookup table replaced by
    direct computation. Inside a block each query is sorted by score (one
    stable argsort: tied scores keep doc order, where the reference's
    ``std::sort`` leaves them unspecified) and its grad/hess are put back in
    doc-slot order by a SCATTER (``.at[order].set``); a host-built row map
    then gathers every row's value out of the classes' concatenated slots.

    The class layout (per class the doc index, its mask and the inverse max
    DCGs, already padded to whole blocks; the row map) is built on the host
    at ``init`` and kept on the device in ``device_arrays``, which the
    training step takes as an ARGUMENT (``GBDT._step_consts``): closed over,
    a table of millions of docs would be baked into the executable as
    constants. ``init`` publishes what one step evaluates: the counters
    ``rank.pair_slots`` (slots of the pair blocks) and ``rank.pairs`` (pairs
    of one query whose labels differ, the slots that carry a lambda), and
    the gauges ``rank.queries`` and ``rank.length_classes``.
    """
    name = "lambdarank"

    QUERY_CHUNK_BUDGET = 1 << 22  # pairwise f32 elements per chunk (~16MB)
    MIN_CLASS = 8                 # the shortest padded query length

    def __init__(self, config: Config):
        super().__init__(config)
        if config.sigmoid <= 0.0:
            Log.fatal("Sigmoid param %f should be greater than zero", config.sigmoid)
        gains = config.label_gain or default_label_gain()
        self.label_gain = np.asarray(gains, dtype=np.float64)
        self.optimize_pos_at = config.max_position

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            Log.fatal("Lambdarank tasks require query information")
        qb = metadata.query_boundaries.astype(np.int64)
        label = metadata.label.astype(np.int64)
        if label.min() < 0 or label.max() >= len(self.label_gain):
            Log.fatal("Label (%d) excceed the max label gain size", int(label.max()))
        self.num_queries = nq = len(qb) - 1
        sizes = np.diff(qb)
        qid = np.repeat(np.arange(nq), sizes)
        offset = np.arange(num_data) - qb[qid]          # a doc's place in its query
        G = len(self.label_gain)
        per_label = np.bincount(qid * G + label, minlength=nq * G).reshape(nq, G)
        # inverse max DCG at k per query (dcg_calculator.cpp semantics): with
        # a query's labels sorted down, label l holds the places
        # [start, end), and the first k places are discounted
        end = np.cumsum(per_label[:, ::-1], axis=1)[:, ::-1]
        start = end - per_label
        k = self.optimize_pos_at
        disc = np.concatenate([[0.0], np.cumsum(1.0 / np.log2(np.arange(k) + 2.0))])
        dcg = (self.label_gain * (disc[np.minimum(end, k)]
                                  - disc[np.minimum(start, k)])).sum(axis=1)
        inv_max_dcg = np.where(dcg > 0.0, 1.0 / np.where(dcg > 0.0, dcg, 1.0), 0.0)

        # the length classes, each padded to whole blocks of queries
        max_m = int(sizes.max()) if nq else 1
        lengths = [self.MIN_CLASS]
        while lengths[-1] < max_m:
            lengths.append(lengths[-1] * 2)
        q_len = np.maximum(self.MIN_CLASS,
                           1 << np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64))
        doc_len = q_len[qid]
        self.classes = []
        classes_dev = []
        pos_of_row = np.zeros(num_data, dtype=np.int64)
        base = slots = 0
        for m in lengths:
            qsel = np.flatnonzero(q_len == m)
            if len(qsel) == 0:
                continue
            chunk = max(1, self.QUERY_CHUNK_BUDGET // (m * m))
            rows = len(qsel) + (-len(qsel)) % chunk     # whole blocks
            place = np.zeros(nq, np.int64)
            place[qsel] = np.arange(len(qsel))
            docs = np.flatnonzero(doc_len == m)
            at = place[qid[docs]] * m + offset[docs]
            doc_idx = np.full(rows * m, num_data, dtype=np.int64)   # sentinel
            doc_idx[at] = docs
            pos_of_row[docs] = base + at
            imd = np.zeros(rows)
            imd[: len(qsel)] = inv_max_dcg[qsel]
            self.classes.append({"m": m, "chunk": chunk, "queries": len(qsel)})
            classes_dev.append({
                "doc_idx": jnp.asarray(doc_idx.reshape(rows, m).astype(np.int32)),
                "mask": jnp.asarray((doc_idx < num_data).reshape(rows, m)),
                "inv_max_dcg": jnp.asarray(imd.astype(np.float32))})
            base += rows * m
            slots += rows * m * m
        self.total_slots = base
        self._pos_of_row_np = pos_of_row
        self.device_arrays = {
            "classes": classes_dev,
            "pos_of_row": jnp.asarray(pos_of_row.astype(np.int32)),
            "label_gain": jnp.asarray(self.label_gain.astype(np.float32)),
            # set by set_row_layout under pre-partitioned block layouts
            "row_positions": None, "slot_of_device_row": None}
        # ordered pairs (high, low) of one query with high's label above
        # low's: the slots of the pair blocks that carry a lambda
        same = int(np.sum(per_label.astype(np.int64) ** 2))
        self.pairs = (int(np.sum(sizes * sizes)) - same) // 2
        self.pair_slots = slots
        from . import observability as obs
        reg = obs.get_registry()
        reg.counter("rank.pair_slots").inc(slots)
        reg.counter("rank.pairs").inc(self.pairs)
        reg.gauge("rank.queries").set(nq)
        reg.gauge("rank.length_classes").set(len(self.classes))

    def device_bytes(self) -> int:
        """HBM the objective holds and its step's gradients pass uses: the
        resident class layout and row map, the classes' grad/hess (8 B a
        slot) and one f32 pair block of the largest class. (The objective
        alone at a 7.3M-doc layout of 11.4M slots, compiled for a described
        v5e, holds 161 MB of temporaries: those two terms.)"""
        resident = sum(int(a.nbytes) for a in jax.tree.leaves(self.device_arrays))
        block = max((c["chunk"] * c["m"] * c["m"] for c in self.classes), default=0)
        return resident + 8 * self.total_slots + 4 * block

    def set_row_layout(self, positions: np.ndarray, npad: int) -> None:
        """Pre-partitioned device layout hook (boosting/gbdt.py): global row
        g lives at padded-device position positions[g], with per-process
        block padding interleaved. Rebuilds the two gathers so gradients()
        reads scores from and writes grad/hess to the real positions —
        the reference analog is Metadata::CheckOrPartition re-indexing
        queries onto the local used-row set (src/io/metadata.cpp:97-127)."""
        positions = np.asarray(positions, np.int64)
        slot = np.full(npad, self.total_slots, dtype=np.int64)  # -> zero slot
        slot[positions] = self._pos_of_row_np
        self.device_arrays = dict(
            self.device_arrays,
            row_positions=jnp.asarray(positions.astype(np.int32)),
            slot_of_device_row=jnp.asarray(slot.astype(np.int32)))

    def _query_grads(self, s, l, mask, inv_max_dcg, label_gain):
        """One padded query: s,l,mask [M]; returns (g, h) [M] in doc order."""
        M = s.shape[0]
        sig = self.config.sigmoid
        neg = jnp.float32(-1e30)
        s_m = jnp.where(mask, s, neg)
        order = jnp.argsort(-s_m)                       # sorted positions -> doc slot
        s_s = s_m[order]
        l_s = jnp.where(mask[order], l[order], 0).astype(jnp.int32)
        valid_s = mask[order]
        gain = label_gain[l_s]
        disc = 1.0 / jnp.log2(jnp.arange(M, dtype=jnp.float32) + 2.0)
        n_valid = jnp.sum(valid_s.astype(jnp.int32))
        best = s_s[0]
        worst = s_s[jnp.maximum(n_valid - 1, 0)]

        ds = s_s[:, None] - s_s[None, :]                # high=i, low=j
        pair_ok = (l_s[:, None] > l_s[None, :]) & valid_s[:, None] & valid_s[None, :]
        dcg_gap = gain[:, None] - gain[None, :]
        paired_disc = jnp.abs(disc[:, None] - disc[None, :])
        delta_ndcg = dcg_gap * paired_disc * inv_max_dcg
        delta_ndcg = jnp.where(best != worst,
                               delta_ndcg / (0.01 + jnp.abs(ds)), delta_ndcg)
        p_lambda = 2.0 / (1.0 + jnp.exp(2.0 * sig * ds))
        p_hess = p_lambda * (2.0 - p_lambda)
        lam = jnp.where(pair_ok, -p_lambda * delta_ndcg, 0.0)
        hes = jnp.where(pair_ok, 2.0 * p_hess * delta_ndcg, 0.0)
        g_sorted = lam.sum(axis=1) - lam.sum(axis=0)
        h_sorted = hes.sum(axis=1) + hes.sum(axis=0)
        # unsort back to doc-slot order
        g = jnp.zeros(M, jnp.float32).at[order].set(g_sorted)
        h = jnp.zeros(M, jnp.float32).at[order].set(h_sorted)
        return g, h

    def gradients(self, score, label, weight):
        # scores may arrive padded to a chunk multiple (boosting/gbdt.py);
        # the query structure only covers the first num_data rows (or, under
        # a pre-partitioned block layout, the positions set_row_layout gave)
        dev = self.device_arrays
        n = self.num_data
        if dev["row_positions"] is not None:
            s_flat = score[0][dev["row_positions"]]
            l_flat = label[dev["row_positions"]]
        else:
            s_flat = score[0, :n]
            l_flat = label[:n]
        s_ext = jnp.concatenate([s_flat, jnp.zeros(1, s_flat.dtype)])
        l_ext = jnp.concatenate([l_flat, jnp.zeros(1, label.dtype)])
        parts = []
        for c, arrays in zip(self.classes, dev["classes"]):
            m, chunk = c["m"], c["chunk"]
            di, mask, imd = arrays["doc_idx"], arrays["mask"], arrays["inv_max_dcg"]
            sq = s_ext[di]
            lq = l_ext[di]

            def batch(args):
                sqc, lqc, maskc, imdc = args
                return jax.vmap(self._query_grads, in_axes=(0, 0, 0, 0, None))(
                    sqc, lqc, maskc, imdc, dev["label_gain"])

            with jax.named_scope("rank.pairwise"):
                gq, hq = jax.lax.map(
                    batch,
                    (sq.reshape(-1, chunk, m), lq.reshape(-1, chunk, m),
                     mask.reshape(-1, chunk, m), imd.reshape(-1, chunk)))
            parts.append((gq.reshape(-1), hq.reshape(-1)))
        g_cat = jnp.concatenate([p[0] for p in parts])
        h_cat = jnp.concatenate([p[1] for p in parts])
        if dev["slot_of_device_row"] is not None:
            # one gather lands grad/hess at their device positions; padding
            # rows point at the appended zero slot
            gx = jnp.concatenate([g_cat, jnp.zeros(1, g_cat.dtype)])
            hx = jnp.concatenate([h_cat, jnp.zeros(1, h_cat.dtype)])
            g = gx[dev["slot_of_device_row"]]
            h = hx[dev["slot_of_device_row"]]
            pad = score.shape[1] - dev["slot_of_device_row"].shape[0]
        else:
            g = g_cat[dev["pos_of_row"]]
            h = h_cat[dev["pos_of_row"]]
            pad = score.shape[1] - n
        if pad:
            g = jnp.concatenate([g, jnp.zeros(pad, g.dtype)])
            h = jnp.concatenate([h, jnp.zeros(pad, h.dtype)])
        g = g[None, :]
        h = h[None, :]
        if weight is not None:
            g = g * weight
            h = h * weight
        return g, h


OBJECTIVE_ALIASES = {
    "regression": "regression", "regression_l2": "regression", "mean_squared_error": "regression",
    "mse": "regression", "l2": "regression", "l2_root": "regression", "rmse": "regression",
    "regression_l1": "regression_l1", "mean_absolute_error": "regression_l1",
    "l1": "regression_l1", "mae": "regression_l1",
    "huber": "huber", "fair": "fair", "poisson": "poisson",
    "binary": "binary",
    "multiclass": "multiclass", "softmax": "multiclass",
    "multiclassova": "multiclassova", "multiclass_ova": "multiclassova",
    "ova": "multiclassova", "ovr": "multiclassova",
    "xentropy": "xentropy", "cross_entropy": "xentropy",
    "xentlambda": "xentlambda", "cross_entropy_lambda": "xentlambda",
    "lambdarank": "lambdarank",
    "none": "none", "null": "none", "custom": "none", "na": "none",
}

_OBJECTIVE_CLASSES = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": RegressionHuber,
    "fair": RegressionFair,
    "poisson": RegressionPoisson,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "xentropy": CrossEntropy,
    "xentlambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
}


def create_objective(config: Config) -> Optional[Objective]:
    """Factory (reference: objective_function.cpp:11-33)."""
    name = OBJECTIVE_ALIASES.get(config.objective)
    if name is None:
        Log.fatal("Unknown objective type name: %s", config.objective)
    if name == "none":
        return None
    return _OBJECTIVE_CLASSES[name](config)
