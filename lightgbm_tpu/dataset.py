"""Binned Dataset: the training matrix as a dense device-resident bin matrix.

Reference counterpart: include/LightGBM/dataset.h:280 (Dataset),
dataset.h:36-248 (Metadata), src/io/dataset_loader.cpp (construction flow).

TPU-first inversion of the reference design: instead of per-feature-group
Bin objects with sparse/dense/4-bit variants and leaf-ordered copies
(src/io/dense_bin.hpp, sparse_bin.hpp, ordered_sparse_bin.hpp), the whole
dataset is ONE dense `uint8/uint16 [num_data, num_features]` array in HBM.
Sparsity is irrelevant to the MXU histogram kernel (a zero bin costs the same
as any bin), so the sparse/dense split and `sparse_threshold` become no-ops
kept only for config compatibility. Per-feature bin counts stay variable;
`bin_offsets` flattens (feature, bin) into one axis for split scans.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .binning import (BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN, MISSING_NONE,
                      MISSING_ZERO, BinMapper, sample_for_binning)
from .config import Config
from .utils.log import Log


class Metadata:
    """Labels / weights / query boundaries / init scores
    (reference: dataset.h:36-248, src/io/metadata.cpp)."""

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label = np.zeros(num_data, dtype=np.float32)
        self.weight: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None
        self.query_weights: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label: Sequence[float]) -> None:
        label = np.asarray(label, dtype=np.float32).reshape(-1)
        if len(label) != self.num_data:
            Log.fatal("Length of label (%d) != num_data (%d)", len(label), self.num_data)
        self.label = label

    def set_weight(self, weight: Optional[Sequence[float]]) -> None:
        if weight is None:
            self.weight = None
            return
        weight = np.asarray(weight, dtype=np.float32).reshape(-1)
        if len(weight) != self.num_data:
            Log.fatal("Length of weight (%d) != num_data (%d)", len(weight), self.num_data)
        self.weight = weight

    def set_group(self, group: Optional[Sequence[int]]) -> None:
        """`group` is per-query sizes (python API) -> boundaries
        (reference: metadata.cpp SetQuery)."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).reshape(-1)
        boundaries = np.concatenate([[0], np.cumsum(group)])
        if boundaries[-1] != self.num_data:
            Log.fatal("Sum of query counts (%d) != num_data (%d)", boundaries[-1], self.num_data)
        self.query_boundaries = boundaries.astype(np.int32)

    def set_init_score(self, init_score: Optional[Sequence[float]]) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.asarray(init_score, dtype=np.float64).reshape(-1)

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


@dataclass
class FeatureInfo:
    """Construction-time info for one used (non-trivial) feature."""
    real_index: int            # column in the raw input
    mapper: BinMapper


@dataclass
class DeferredBinning:
    """Raw dense rows held in place of a materialized ``X_binned``
    (``tpu_ingest=device|auto``): the booster bins them ON DEVICE
    (ops/ingest.py) straight into the residency layout, and the host bin
    matrix only ever exists if some consumer explicitly reads the
    ``X_binned`` property (EFB materialization, save_binary, streaming
    residency — each a transparent host fallback through the oracle).
    ``raw`` stays referenced while deferred — the memory trade is the raw
    f32/f64 matrix instead of u8/u16 codes, bounded by the same host RAM
    that held the raw input to begin with."""
    raw: np.ndarray            # [num_data, num_total_features] dense
    code_dtype: np.dtype       # uint8 | uint16 — decided at construction


class MetadataDuckTyping:
    """Duck-typed reference-Dataset surface over ``self.metadata`` — custom
    objectives and eval functions written against the reference contract
    (fobj(preds, train_data) -> grad, hess; feval(preds, eval_data);
    reference basic.py Dataset.get_label) receive objects with this mixin
    from the boosting loop."""

    def get_label(self):
        return self.metadata.label

    def get_weight(self):
        return self.metadata.weight

    def get_group(self):
        qb = self.metadata.query_boundaries
        return None if qb is None else np.diff(qb)

    def get_init_score(self):
        return self.metadata.init_score


class ConstructedDataset(MetadataDuckTyping):
    """The binned dataset (reference Dataset, dataset.h:280).

    Attributes
    ----------
    X_binned : np.ndarray [num_data, num_features] uint8|uint16
        per-feature bin codes of the used (non-trivial) features.
    mappers : list[BinMapper], one per used feature.
    real_feature_idx : used feature -> raw column index
        (reference: dataset.h:552 real_feature_idx_).
    used_feature_map : raw column -> used feature index or -1
        (reference: dataset.h:543 used_feature_map_).
    bin_offsets : int32 [num_features+1]
        flattened (feature, bin) offsets; total_bins = bin_offsets[-1].
    """

    def __init__(self, X_binned: Optional[np.ndarray],
                 features: List[FeatureInfo],
                 num_total_features: int, metadata: Metadata,
                 feature_names: List[str], config: Config,
                 deferred: Optional[DeferredBinning] = None):
        # X_binned=None defers host binning (DeferredBinning): shape and
        # code dtype are pinned NOW so every metadata read stays free of a
        # materialization, and the X_binned property bins lazily through
        # the host oracle only if something actually needs host codes
        self._X_binned = X_binned
        self._deferred = deferred if X_binned is None else None
        if X_binned is not None:
            self._shape = tuple(X_binned.shape)
            self._code_dtype = X_binned.dtype
        else:
            assert deferred is not None
            self._shape = (metadata.num_data, max(len(features), 1))
            self._code_dtype = np.dtype(deferred.code_dtype)
        self.mappers = [f.mapper for f in features]
        self.real_feature_idx = np.array([f.real_index for f in features], dtype=np.int32)
        self.used_feature_map = np.full(num_total_features, -1, dtype=np.int32)
        for inner, f in enumerate(features):
            self.used_feature_map[f.real_index] = inner
        self.num_total_features = num_total_features
        self.metadata = metadata
        self.feature_names = feature_names
        self.config = config
        counts = np.array([m.num_bin for m in self.mappers], dtype=np.int64)
        self.bin_offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
        self.num_bins_per_feature = counts.astype(np.int32)
        # raw f32 slice of the used features (linear_tree=true only,
        # ops/linear.py): the per-leaf ridge fits read raw values, which
        # binning otherwise discards — construct_dataset fills it when the
        # config asks for linear trees; None everywhere else (zero cost)
        self.X_raw: Optional[np.ndarray] = None
        # sharded device residency (boosting/gbdt.py): the padded binned
        # code matrix placed on the booster's mesh, cached per placement
        # key so the dataset's device residency is first-class — every
        # booster built over the same mesh/padding reuses the SAME device
        # buffers instead of re-uploading N*F bytes per construction
        self._device_cache: Dict[tuple, object] = {}

    # -- lazy bin matrix (tpu_ingest: ops/ingest.py) --------------------------

    @property
    def X_binned(self) -> np.ndarray:
        """The host bin matrix. Under deferred ingest the first read
        materializes it through the host oracle (single pass per column,
        value_to_bin ``out=``) — every legacy consumer keeps working, it
        just pays host binning the way it always did."""
        if self._X_binned is None:
            self._X_binned = self._materialize_host()
        return self._X_binned

    @X_binned.setter
    def X_binned(self, value: np.ndarray) -> None:
        self._X_binned = value
        self._deferred = None
        self._shape = tuple(value.shape)
        self._code_dtype = value.dtype

    @property
    def deferred(self) -> bool:
        """True while binning is deferred (no host ``X_binned`` exists)."""
        return self._X_binned is None

    @property
    def code_dtype(self) -> np.dtype:
        """Bin-code dtype — readable without materializing."""
        return self._code_dtype

    def deferred_raw(self) -> Optional[np.ndarray]:
        """The raw matrix backing a still-deferred dataset (None once
        materialized) — the device ingest input."""
        return self._deferred.raw if self._deferred is not None else None

    def bin_rows(self, rows: np.ndarray) -> np.ndarray:
        """Host-oracle codes of specific rows, BYTE-identical to
        ``np.ascontiguousarray(self.X_binned[rows])`` whether or not the
        matrix is materialized — the checkpoint data fingerprint and the
        EFB planning sample read through this so their bytes are invariant
        to ``tpu_ingest`` (the knob is checkpoint-VOLATILE)."""
        if self._X_binned is not None:
            return np.ascontiguousarray(self._X_binned[rows])
        sub = self._deferred.raw[rows]
        out = np.zeros((sub.shape[0], self.num_features), self._code_dtype)
        for inner, real in enumerate(self.real_feature_idx):
            self.mappers[inner].value_to_bin(sub[:, real], out=out[:, inner])
        return out

    def _materialize_host(self) -> np.ndarray:
        d = self._deferred
        Log.info("deferred binning: materializing host X_binned "
                 "(%d x %d %s) through the host oracle",
                 self._shape[0], self._shape[1], self._code_dtype)
        X = bin_dense_host(d.raw, self.mappers,
                           np.asarray(self.real_feature_idx),
                           self._code_dtype, self._shape[0])
        self._deferred = None
        return X

    # -- shape ----------------------------------------------------------------

    @property
    def num_data(self) -> int:
        return int(self._shape[0])

    @property
    def num_features(self) -> int:
        return int(self._shape[1])

    @property
    def total_bins(self) -> int:
        return int(self.bin_offsets[-1])

    @property
    def max_num_bin(self) -> int:
        return int(self.num_bins_per_feature.max()) if self.num_features else 1

    # -- feature metadata for the split kernels -------------------------------

    def feature_meta_arrays(self) -> Dict[str, np.ndarray]:
        """Static per-feature arrays consumed by the split-finding kernel."""
        F = self.num_features
        is_categorical = np.array(
            [m.bin_type == BIN_CATEGORICAL for m in self.mappers], dtype=bool)
        missing_code = np.array(
            [{MISSING_NONE: 0, MISSING_ZERO: 1, MISSING_NAN: 2}[m.missing_type]
             for m in self.mappers], dtype=np.int32)
        default_bin = np.array([m.default_bin for m in self.mappers], dtype=np.int32)
        return {
            "is_categorical": is_categorical,
            "missing_code": missing_code,
            "default_bin": default_bin,
            "num_bins": self.num_bins_per_feature,
            "bin_offsets": self.bin_offsets,
        }

    # -- sharded device residency (docs/TPU-Performance.md, multichip) --------

    def device_put_cached(self, key: tuple, build):
        """Device residency cache for this dataset's immutable training
        arrays (the binned code matrix and the padding mask).

        ``key`` must capture everything that determines the placed array —
        the ParallelContext residency key (mesh devices + strategy axis),
        padded shape, dtype, and the EFB bundle signature — and ``build()``
        materializes it (host pad + ``device_put``/``NamedSharding``). The
        first booster pays the host->device transfer; every later booster
        over the same mesh gets the SAME on-device buffers (safe because
        these arrays travel as non-donated step constants,
        boosting/gbdt.py ``_STEP_CONSTS``). Mutable metadata (labels,
        weights) is deliberately NOT cached — ``set_label`` after
        construction must keep working.

        One entry per logical name (``key[0]``): switching the same Dataset
        to a different mesh/strategy/padding evicts the previous placement
        rather than pinning a second full device copy for the Dataset's
        lifetime (live boosters keep their own references; only the cache
        slot is bounded)."""
        arr = self._device_cache.get(key)
        if arr is None:
            for stale in [k for k in self._device_cache if k[0] == key[0]]:
                del self._device_cache[stale]
            arr = build()
            self._device_cache[key] = arr
        return arr

    # -- alignment (valid sets share the train mappers) -----------------------

    def bin_raw(self, data: np.ndarray) -> np.ndarray:
        """Bin a raw feature matrix with THIS dataset's mappers (the analog of
        LoadFromFileAlignWithOtherDataset, dataset_loader.cpp:221)."""
        out = np.zeros((data.shape[0], self.num_features), dtype=self.code_dtype)
        if hasattr(data, "tocsc"):
            csc = data.tocsc()
            for inner, real in enumerate(self.real_feature_idx):
                m = self.mappers[inner]
                rows, vals = _csc_column(csc, real)
                # default_bin IS the zero bin (asserted at mapper
                # construction) — no per-column value_to_bin(0) re-run
                out[:, inner] = out.dtype.type(m.default_bin)
                if len(rows):
                    out[rows, inner] = m.value_to_bin(vals)
            return out
        data = np.asarray(data)
        for inner, real in enumerate(self.real_feature_idx):
            self.mappers[inner].value_to_bin(data[:, real], out=out[:, inner])
        return out

    # -- binary serialization (reference: Dataset::SaveBinaryFile,
    #    dataset.cpp:496; auto-detect load, dataset_loader.cpp:265) ----------

    def save_binary(self, path: str) -> None:
        import pickle
        with open(path, "wb") as fh:
            pickle.dump({
                "format": "lightgbm_tpu.dataset.v1",
                "X_binned": self.X_binned,
                "mappers": self.mappers,
                "real_feature_idx": self.real_feature_idx,
                "num_total_features": self.num_total_features,
                "feature_names": self.feature_names,
                "label": self.metadata.label,
                "weight": self.metadata.weight,
                "query_boundaries": self.metadata.query_boundaries,
                "init_score": self.metadata.init_score,
                "config": self.config.to_dict(),
                "X_raw": self.X_raw,
            }, fh, protocol=pickle.HIGHEST_PROTOCOL)

    @classmethod
    def load_binary(cls, path: str) -> "ConstructedDataset":
        import pickle
        with open(path, "rb") as fh:
            blob = pickle.load(fh)
        if blob.get("format") != "lightgbm_tpu.dataset.v1":
            Log.fatal("Not a lightgbm_tpu binary dataset file: %s", path)
        meta = Metadata(blob["X_binned"].shape[0])
        meta.set_label(blob["label"])
        meta.set_weight(blob["weight"])
        meta.query_boundaries = blob["query_boundaries"]
        meta.init_score = blob["init_score"]
        features = [FeatureInfo(int(r), m)
                    for r, m in zip(blob["real_feature_idx"], blob["mappers"])]
        ds = cls(blob["X_binned"], features, blob["num_total_features"], meta,
                 blob["feature_names"], Config.from_params(blob["config"]))
        ds.X_raw = blob.get("X_raw")   # present iff saved under linear_tree
        return ds


def _map_find_bin(active: List[int], find_one) -> Dict[int, "BinMapper"]:
    """``find_one`` over every feature in ``active`` on a thread pool —
    numpy releases the GIL in the unique/searchsorted passes that dominate
    ``BinMapper.find_bin``, so quantile finding goes parallel across
    features (ROADMAP item 1's host half). The result dict's insertion
    order is EXACTLY ``active`` order regardless of completion order
    (``Executor.map`` yields in input order; pinned by test)."""
    workers = min(16, os.cpu_count() or 1, len(active))
    if workers <= 1:
        return {j: find_one(j) for j in active}
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(workers) as pool:
        return dict(zip(active, pool.map(find_one, active)))


def _find_bins(active: List[int], find_one,
               config: Optional[Config] = None) -> Dict[int, "BinMapper"]:
    """Run FindBin for every active feature — feature-sharded across hosts
    under DISTRIBUTED TRAINING (reference distributed bin finding:
    feature-partitioned FindBin + Allgather of serialized BinMappers,
    dataset_loader.cpp:820-899). Each process computes the mappers of the
    features it owns (round-robin by rank) and the pickled shards are
    exchanged host-side through jax's coordination-service KV store, so
    every process ends with identical mappers.

    Gated on the lightgbm network config (num_machines > 1), NOT on ambient
    jax state: a user's multi-process jax program that trains on a subset
    of ranks must not enter a collective here."""
    if config is None or getattr(config, "num_machines", 1) <= 1:
        return _map_find_bin(active, find_one)
    from .parallel import comm
    client = comm.distributed_client()
    import jax
    if client is None or jax.process_count() <= 1:
        return _map_find_bin(active, find_one)

    rank, world = jax.process_index(), jax.process_count()
    timeout_ms = int(getattr(config, "time_out", 120)) * 60 * 1000
    mine = _map_find_bin([j for j in active if j % world == rank], find_one)
    # host_allgather owns the KV exchange end to end — per-peer retry with
    # bounded backoff, typed PeerLostError attribution, chaos injection,
    # done-barrier + key cleanup (R013: raw client calls stay in comm.py)
    shards = comm.host_allgather(mine, "binmappers", timeout_ms=timeout_ms)
    out: Dict[int, BinMapper] = {}
    for shard in shards:
        out.update(shard)
    return out


def _csc_column(csc, j: int) -> Tuple[np.ndarray, np.ndarray]:
    """(row_indices, float64_values) of column ``j`` via indptr slicing —
    works for both scipy.sparse csc_matrix and the newer csc_array (which
    has no ``getcol``)."""
    lo, hi = csc.indptr[j], csc.indptr[j + 1]
    return csc.indices[lo:hi], np.asarray(csc.data[lo:hi], dtype=np.float64)


def _parse_column_spec(spec: str, feature_names: List[str]) -> List[int]:
    """Parse 'name:a,name:b' or '0,1,2' column specs
    (reference: dataset_loader.cpp column resolution)."""
    if not spec:
        return []
    out = []
    for tok in str(spec).split(","):
        tok = tok.strip()
        if not tok:
            continue
        if tok.startswith("name:"):
            name = tok[5:]
            if name not in feature_names:
                Log.fatal("Column name %s not found", name)
            out.append(feature_names.index(name))
        else:
            out.append(int(tok))
    return out


def construct_dataset(
    data: np.ndarray,
    label: Optional[Sequence[float]],
    config: Config,
    weight: Optional[Sequence[float]] = None,
    group: Optional[Sequence[int]] = None,
    init_score: Optional[Sequence[float]] = None,
    feature_names: Optional[List[str]] = None,
    categorical_features: Optional[Sequence[Union[int, str]]] = None,
) -> ConstructedDataset:
    """Build a ConstructedDataset from a raw numpy matrix.

    Mirrors DatasetLoader::ConstructBinMappersFromTextData
    (dataset_loader.cpp:748-903): sample -> FindBin per feature -> drop
    trivial features -> materialize bin codes.
    """
    from . import observability as obs
    # the table as binning reads it, and which columns are what
    with obs.setup_span("dataset.columns"):
        sparse = hasattr(data, "tocsc")
        if sparse:
            data = data.tocsc()            # columnwise access for binning
        else:
            data = np.ascontiguousarray(data)
        if data.ndim != 2:
            Log.fatal("Training data must be 2-dimensional")
        num_data, num_total_features = data.shape
        if feature_names is None:
            feature_names = [f"Column_{i}" for i in range(num_total_features)]

        # resolve categorical / ignored columns
        cat_set = set()
        if categorical_features is not None:
            for c in categorical_features:
                cat_set.add(feature_names.index(c) if isinstance(c, str) else int(c))
        cat_set.update(_parse_column_spec(config.categorical_column, feature_names))
        ignore_set = set(_parse_column_spec(config.ignore_column, feature_names))

    # bin finding: the row sample, then the quantiles of each feature
    with obs.setup_span("dataset.find_bins"):
        # sampling (dataset_loader.cpp:688-746)
        _, per_feature_samples = sample_for_binning(
            data, config.bin_construct_sample_cnt, config.data_random_seed)
        total_sample_cnt = min(num_data, config.bin_construct_sample_cnt)
        # reference: filter_cnt = min_data_in_leaf * sample / num_data (dataset_loader.cpp:495)
        filter_cnt = int(config.min_data_in_leaf * total_sample_cnt / max(num_data, 1))

        def _find_one(j: int) -> BinMapper:
            mapper = BinMapper()
            bin_type = BIN_CATEGORICAL if j in cat_set else BIN_NUMERICAL
            mapper.find_bin(per_feature_samples[j], total_sample_cnt,
                            config.max_bin, config.min_data_in_bin, filter_cnt,
                            bin_type, config.use_missing, config.zero_as_missing)
            return mapper

        active = [j for j in range(num_total_features) if j not in ignore_set]
        mappers_by_idx = _find_bins(active, _find_one, config)
    features: List[FeatureInfo] = [
        FeatureInfo(j, mappers_by_idx[j]) for j in active
        if not mappers_by_idx[j].is_trivial]
    if not features:
        Log.warning("There are no meaningful features, as all feature values are constant.")

    dtype = np.uint8 if all(f.mapper.num_bin <= 256 for f in features) else np.uint16

    # whether the device can bin these rows (for float64 input that is
    # the round-trip check, ``dataset.lossless_check``, a child of this)
    with obs.setup_span("dataset.ingest_check"):
        deferred = _maybe_defer(data, features, config, dtype, num_data, sparse)
    # host binning: nothing where binning was deferred to the device
    with obs.setup_span("dataset.bin_host"):
        if deferred is not None:
            X_binned = None
        elif sparse:
            X_binned = np.zeros((num_data, max(len(features), 1)), dtype=dtype)

            def _bin_column(inner_f):
                # bin the implicit zeros once, scatter only the stored values
                # (the float matrix is never densified; the dense uint8 bin
                # matrix IS the design's storage — dataset.py:6-14); the zero
                # bin is default_bin (asserted at mapper construction), and
                # the fancy-index assignment casts to the output dtype in one
                # pass
                inner, f = inner_f
                rows, vals = _csc_column(data, f.real_index)
                X_binned[:, inner] = dtype(f.mapper.default_bin)
                if len(rows):
                    X_binned[rows, inner] = f.mapper.value_to_bin(vals)

            if num_data * max(len(features), 1) > 8_000_000 and len(features) > 1:
                from concurrent.futures import ThreadPoolExecutor
                workers = min(16, os.cpu_count() or 1, len(features))
                with ThreadPoolExecutor(workers) as pool:
                    list(pool.map(_bin_column, enumerate(features)))
            else:
                for item in enumerate(features):
                    _bin_column(item)
        else:
            X_binned = bin_dense_host(
                data, [f.mapper for f in features],
                np.array([f.real_index for f in features], np.int64),
                dtype, num_data)

    with obs.setup_span("dataset.metadata"):
        metadata = Metadata(num_data)
        if label is not None:
            metadata.set_label(label)
        metadata.set_weight(weight)
        metadata.set_group(group)
        metadata.set_init_score(init_score)

        ds = ConstructedDataset(X_binned, features, num_total_features, metadata,
                                feature_names, config, deferred=deferred)
        if getattr(config, "linear_tree", False):
            ds.X_raw = extract_raw_slice(
                data, [f.real_index for f in features], num_data)
    return ds


def bin_dense_host(data: np.ndarray, mappers, real_indices: np.ndarray,
                   dtype, num_data: int) -> np.ndarray:
    """Dense host binning: one ``value_to_bin`` pass per column, written
    straight into the output dtype (``out=``) — no int32 intermediate +
    astype + assignment-copy chain. This IS the host oracle the device
    ingest path (ops/ingest.py) is tested against bit-for-bit, and the
    lazy materialization target of a deferred dataset."""
    F = max(len(real_indices), 1)
    X_binned = np.zeros((num_data, F), dtype=dtype)
    big = num_data * F > 8_000_000

    def _bin_column(inner: int):
        col = data[:, real_indices[inner]]
        if big:
            # one contiguous copy per column: value_to_bin makes several
            # full passes and a stride-F read thrashes cache on each
            col = np.ascontiguousarray(col)
        mappers[inner].value_to_bin(col, out=X_binned[:, inner])

    # numpy releases the GIL in the heavy passes — threads help on
    # multi-core hosts (the analog of the reference's OMP row-parallel push
    # loop, dataset_loader.cpp:906-1101) and pick 1 worker on 1-core boxes
    if big and len(real_indices) > 1:
        from concurrent.futures import ThreadPoolExecutor
        workers = min(16, os.cpu_count() or 1, len(real_indices))
        with ThreadPoolExecutor(workers) as pool:
            list(pool.map(_bin_column, range(len(real_indices))))
    else:
        for inner in range(len(real_indices)):
            _bin_column(inner)
    return X_binned


# minimum rows before tpu_ingest=auto defers to device binning: below this
# the jit compile + chunk dispatch overhead outweighs the host pass
_AUTO_DEFER_MIN_ROWS = 65536


def _maybe_defer(data, features, config: Config, dtype, num_data: int,
                 sparse: bool) -> Optional[DeferredBinning]:
    """Decide at construction whether to SKIP host binning and hand the
    booster raw rows for on-device ingest (ops/ingest.py). Numpy-only:
    the eligibility check never touches jax. ``device`` defers whenever
    the input is eligible (warns and falls back otherwise); ``auto``
    additionally requires enough rows to amortize the compile."""
    mode = getattr(config, "tpu_ingest", "host")
    if mode not in ("device", "auto") or sparse or not features:
        return None
    from .ops.ingest import device_ingest_blocker
    blocker = device_ingest_blocker(data, [f.mapper for f in features])
    if blocker is None and mode == "auto" and num_data < _AUTO_DEFER_MIN_ROWS:
        blocker = (f"tpu_ingest=auto defers only at >= "
                   f"{_AUTO_DEFER_MIN_ROWS} rows (got {num_data})")
    if blocker is not None:
        if mode == "device":
            Log.warning("tpu_ingest=device: falling back to host binning "
                        "(%s)", blocker)
        else:
            Log.debug("tpu_ingest=auto: host binning (%s)", blocker)
        return None
    Log.debug("tpu_ingest=%s: deferring binning to device ingest "
              "(%d rows x %d features)", mode, num_data, len(features))
    return DeferredBinning(raw=data, code_dtype=np.dtype(dtype))


def extract_raw_slice(data, real_indices, num_data: int) -> np.ndarray:
    """[N, used_features] f32 raw values (NaN preserved) for linear-tree
    fits — the used-feature column slice of the input, densified from
    sparse inputs column-by-column (implicit zeros stay numeric 0.0, so
    only true NaNs take the constant-leaf fallback)."""
    out = np.zeros((num_data, max(len(real_indices), 1)), np.float32)
    if hasattr(data, "tocsc"):
        csc = data.tocsc()
        for inner, real in enumerate(real_indices):
            rows, vals = _csc_column(csc, real)
            if len(rows):
                out[rows, inner] = vals.astype(np.float32)
        return out
    data = np.asarray(data)
    for inner, real in enumerate(real_indices):
        out[:, inner] = np.asarray(data[:, real], np.float32)
    return out
