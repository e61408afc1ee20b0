"""User-facing Dataset and Booster, mirroring the reference Python package
(python-package/lightgbm/basic.py: Dataset at :556, Booster at :1234) — but
backed by the TPU pipeline instead of ctypes into lib_lightgbm.so.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Union

import numpy as np

from . import observability as obs
from .config import Config
from .dataset import ConstructedDataset, Metadata, construct_dataset
from .tree import Tree
from .utils.log import Log


def _is_sparse(data) -> bool:
    """scipy CSR/CSC/COO duck-check without importing scipy."""
    return hasattr(data, "tocsr") and hasattr(data, "tocsc")


def _data_from_pandas(df, pandas_categorical=None):
    """DataFrame -> float64 matrix, mapping `category` dtype columns to their
    category codes (reference basic.py:226-268). At train time the per-column
    category lists are recorded; at predict time the recorded lists re-map so
    codes agree with training (unseen categories become NaN/missing).

    Returns (array, feature_names, cat_col_names, pandas_categorical).
    """
    cat_cols = [c for c in df.columns if str(df[c].dtype) == "category"]
    if pandas_categorical is None:                    # training
        pandas_categorical = [list(df[c].cat.categories) for c in cat_cols]
    elif len(cat_cols) != len(pandas_categorical):
        raise ValueError("train and predict data have different categorical "
                         "columns")
    if cat_cols:
        df = df.copy()
        for c, cats in zip(cat_cols, pandas_categorical):
            codes = df[c].cat.set_categories(cats).cat.codes.astype(np.float64)
            df[c] = codes.where(codes >= 0, np.nan)   # unseen/NaN -> missing
    arr = df.values.astype(np.float64, copy=False)
    return arr, [str(c) for c in df.columns], [str(c) for c in cat_cols], \
        pandas_categorical


def _to_2d_float(data):
    if _is_sparse(data):
        # keep sparse: binning densifies to uint8 bin codes columnwise
        # without ever materializing the float matrix (reference accepts
        # CSR/CSC via LGBM_DatasetCreateFromCSR/CSC, c_api.cpp:471+)
        return data.tocsr(), None
    arr = np.asarray(data)
    if arr.dtype != np.float32:
        # float32 rows stay as they came (no copy): every reader widens the
        # values it looks at (the bin-finding sample, a column, a chunk),
        # and f32 -> f64 is exact, so bins, codes and trees are the float64
        # copy's. Everything else becomes float64, as the reference reads it
        with obs.setup_span("dataset.to_float"):
            arr = np.asarray(arr, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(1, -1)
    return arr, None


class Dataset:
    """Lazily-constructed training dataset (reference basic.py:556).

    Binning happens at first use (`_lazy_construct`, reference basic.py:698);
    validation sets built with `reference=` share the training set's
    BinMappers (the analog of LoadFromFileAlignWithOtherDataset).
    """

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = False, silent: bool = False):
        self._binary_path: Optional[str] = None
        self._stream_path: Optional[str] = None
        if isinstance(data, str):
            from .config import resolve_aliases
            from .io.file_io import is_binary_dataset, load_data_file
            resolved = resolve_aliases(dict(params or {}))
            if is_binary_dataset(data):
                # binary dataset auto-detect (dataset_loader.cpp:265)
                self._binary_path = data
                data = np.zeros((0, 1))
            elif resolved.get("use_two_round_loading"):
                # two-round streaming load, deferred to construct()
                self._stream_path = data
                data = np.zeros((0, 1))
            else:
                data, file_label, side = load_data_file(data, resolved)
                if label is None:
                    label = file_label
                if weight is None:
                    weight = side.get("weight")
                if group is None:
                    group = side.get("group")
                if init_score is None:
                    init_score = side.get("init_score")
                if feature_name == "auto" and side.get("feature_names"):
                    feature_name = side["feature_names"]
        self.pandas_categorical = None
        if hasattr(data, "values") and hasattr(data, "columns"):   # DataFrame
            # a valid set aligned to a training set must encode categories
            # with the TRAINING set's category lists, not its own frame's
            # (codes are order-dependent; reference basic.py:226-268)
            ref_pc = getattr(reference, "pandas_categorical", None)
            arr, names, cat_cols, self.pandas_categorical = _data_from_pandas(
                data, ref_pc)
            self.raw_data, inferred_names = arr, names
            if categorical_feature == "auto" and cat_cols:
                categorical_feature = cat_cols
        else:
            self.raw_data, inferred_names = _to_2d_float(data)
        self.label = None if label is None else np.asarray(label).reshape(-1)
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name if feature_name != "auto" else inferred_names
        self.categorical_feature = None if categorical_feature == "auto" else categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._constructed: Optional[ConstructedDataset] = None
        self._binned_aligned: Optional[np.ndarray] = None

    # -- construction --------------------------------------------------------

    def construct(self, config: Optional[Config] = None) -> "Dataset":
        if self._constructed is not None or self._binned_aligned is not None:
            return self
        # the training set's is one of set-up's two wholes (the other:
        # ``booster.init``); a valid set's is a boundary of its own, so that
        # one attached after ``booster.init`` leaves ``setup.*`` the
        # training set's
        with obs.setup_span("dataset.construct" if self.reference is None
                            else "dataset.construct_valid"):
            self._construct(config)
        return self

    def _construct(self, config: Optional[Config]) -> None:
        if self._binary_path is not None:
            self._constructed = ConstructedDataset.load_binary(self._binary_path)
            self.label = self._constructed.metadata.label
            return
        if self._stream_path is not None:
            from .io.file_io import stream_construct_dataset
            cfg = config or Config.from_params(self.params)
            self._constructed = stream_construct_dataset(
                self._stream_path, cfg,
                feature_names=None if self.feature_name in (None, "auto")
                else self.feature_name,
                categorical_features=self.categorical_feature)
            self.label = self._constructed.metadata.label
            return
        if self.reference is not None:
            ref = self.reference
            ref.construct(config)
            self._binned_aligned = ref._constructed.bin_raw(self.raw_data)
            meta = Metadata(self.raw_data.shape[0])
            if self.label is not None:
                meta.set_label(self.label)
            meta.set_weight(self.weight)
            meta.set_group(self.group)
            meta.set_init_score(self.init_score)
            self._metadata = meta
        else:
            cfg = config or Config.from_params(self.params)
            self._constructed = construct_dataset(
                self.raw_data, self.label, cfg,
                weight=self.weight, group=self.group,
                init_score=self.init_score,
                feature_names=self.feature_name,
                categorical_features=self.categorical_feature)
        if self.free_raw_data:
            self.raw_data = None

    @property
    def constructed(self) -> ConstructedDataset:
        if self._constructed is None:
            self.construct()
        return self._constructed

    # -- introspection (reference basic.py Dataset API) ----------------------

    def num_data(self) -> int:
        if self._constructed is None and (self._binary_path or self._stream_path):
            self.construct()
        if self._constructed is not None:
            return self._constructed.num_data
        return self.raw_data.shape[0]

    def num_feature(self) -> int:
        if self._constructed is None and (self._binary_path or self._stream_path):
            self.construct()
        if self._constructed is not None:
            return self._constructed.num_total_features
        return self.raw_data.shape[1]

    def get_label(self):
        return self.label

    def _meta_sink(self):
        """The metadata object live state writes through to: a constructed
        training set's, or a reference-aligned valid set's (basic
        construct() stores the latter in _metadata)."""
        if self._constructed is not None:
            return self._constructed.metadata
        return getattr(self, "_metadata", None)

    def set_label(self, label):
        self.label = None if label is None else np.asarray(label).reshape(-1)
        sink = self._meta_sink()
        if sink is not None and self.label is not None:
            sink.set_label(self.label)
        return self

    def get_weight(self):
        return self.weight

    def set_weight(self, weight):
        self.weight = weight
        sink = self._meta_sink()
        if sink is not None:
            sink.set_weight(weight)
        return self

    def set_group(self, group):
        self.group = group
        sink = self._meta_sink()
        if sink is not None:
            sink.set_group(group)
        return self

    def set_init_score(self, init_score):
        self.init_score = init_score
        sink = self._meta_sink()
        if sink is not None:
            sink.set_init_score(init_score)
        return self

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def get_field(self, name):
        return {"label": self.label, "weight": self.weight,
                "group": self.group, "init_score": self.init_score}[name]

    def set_field(self, name, data):
        """Generic field setter (reference basic.py Dataset.set_field /
        LGBM_DatasetSetField): routes to the typed setters."""
        setter = {"label": self.set_label, "weight": self.set_weight,
                  "group": self.set_group,
                  "init_score": self.set_init_score}.get(name)
        if setter is None:
            raise ValueError(f"Unknown field name: {name}")
        return setter(data)

    def set_reference(self, reference: "Dataset") -> "Dataset":
        """Bin this dataset with `reference`'s mappers (reference
        basic.py set_reference). Must precede construction."""
        if self._constructed is not None or self._binned_aligned is not None:
            if self.reference is reference:
                return self
            raise ValueError(
                "Cannot set reference after the dataset was constructed")
        ref_pc = getattr(reference, "pandas_categorical", None) or None
        if (self.pandas_categorical or None) is not None and \
                self.pandas_categorical != ref_pc:
            # category CODES were fixed at __init__ against this frame's
            # (or the old reference's) category lists; re-referencing would
            # bin those codes with mappers from a different list order
            raise ValueError(
                "Cannot set_reference on a pandas-categorical dataset "
                "encoded against different category lists — rebuild the "
                "Dataset with reference= instead")
        self.reference = reference
        return self

    def get_ref_chain(self, ref_limit: int = 100):
        """Set of datasets reachable through .reference links
        (reference basic.py:878)."""
        head, chain = self, set()
        while head is not None and len(chain) < ref_limit:
            if head in chain:
                break
            chain.add(head)
            head = head.reference
        return chain

    def set_feature_name(self, feature_name) -> "Dataset":
        if feature_name is not None and feature_name != "auto":
            feature_name = list(feature_name)
            if self._constructed is not None:
                nf = self._constructed.num_total_features
            elif self.raw_data is not None and self.raw_data.shape[0] > 0:
                nf = self.raw_data.shape[1]
            else:           # binary/streaming placeholder raw_data
                nf = None
            if nf is not None and len(feature_name) != nf:
                raise ValueError(
                    f"Length of feature_name ({len(feature_name)}) does "
                    f"not equal the number of features ({nf})")
            self.feature_name = feature_name
            if self._constructed is not None:
                self._constructed.feature_names = list(feature_name)
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """Must precede construction (binning depends on it), like the
        reference's re-construct warning path."""
        if isinstance(categorical_feature, str) and \
                categorical_feature == "auto":
            return self     # auto = keep the auto-derived setting
        old = self.categorical_feature
        same = (categorical_feature is old
                or (old is not None and categorical_feature is not None
                    and list(categorical_feature) == list(old)))
        if (self._constructed is not None
                or self._binned_aligned is not None) and not same:
            raise ValueError("Cannot change categorical_feature after the "
                             "dataset was constructed")
        self.categorical_feature = categorical_feature
        return self

    def save_binary(self, filename: str) -> "Dataset":
        self.constructed.save_binary(filename)
        return self

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, params=params)

    def subset(self, used_indices, params=None) -> "Dataset":
        idx = np.asarray(used_indices)
        init_score = None
        if self.init_score is not None:
            is_arr = np.asarray(self.init_score)
            init_score = is_arr[idx] if is_arr.ndim == 1 and len(is_arr) == self.num_data() \
                else is_arr
        group = None
        if self.group is not None:
            # Grouped data subsets at query granularity only (reference
            # engine.py _make_n_folds folds by group): every query must be
            # entirely in or out of `used_indices`, and rows of a query must
            # stay together so the new group array is well-formed.
            sizes = np.asarray(self.group, dtype=np.int64)
            qid = np.repeat(np.arange(len(sizes)), sizes)        # row -> query
            if len(qid) != self.num_data():
                Log.fatal("group sizes do not sum to num_data")
            take = np.zeros(len(sizes), bool)
            take[np.unique(qid[idx])] = True
            full = np.flatnonzero(take)
            if len(idx) != int(sizes[full].sum()) or np.any(np.diff(qid[idx]) < 0):
                Log.fatal("Cannot subset a grouped Dataset except by whole "
                          "queries in query order (ranking cv folds at query "
                          "granularity)")
            group = sizes[full]
        return Dataset(self.raw_data[idx],
                       label=None if self.label is None else self.label[idx],
                       weight=None if self.weight is None else np.asarray(self.weight)[idx],
                       init_score=init_score,
                       group=group,
                       params=params or self.params,
                       feature_name=self.feature_name,
                       categorical_feature=self.categorical_feature)


class Booster:
    """Trained model handle (reference basic.py:1234).

    Training happens through `train()`/`update()`; the trained forest lives as
    host `Tree` objects for prediction/serialization while training state
    (scores, binned data) stays on device inside the internal GBDT driver.
    """

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None, model_str: Optional[str] = None,
                 silent: bool = False):
        self.params = dict(params or {})
        self.config = Config.from_params(self.params)
        self._gbdt = None
        self.trees: List[Tree] = []          # flattened tree list (iter-major)
        self._forest_rev = 0                 # bumped whenever trees change
        self.num_model_per_iteration = 1
        self.best_iteration = 0
        self.best_score: Dict = {}
        self.feature_names: List[str] = []
        self.num_total_features = 0
        self.mappers = []
        self.init_score_value = 0.0
        self.pandas_categorical = None
        self.eval_history: Dict = {}         # dataset -> metric -> [values]
        self._attr: Dict[str, str] = {}
        self._train_data_name = "training"
        self._valid_registry: List = []      # (Dataset, name) identity pairs
        if model_file is not None:
            from .io.model_text import load_model_file
            load_model_file(self, model_file)
        elif model_str is not None:
            from .io.model_text import load_model_string
            load_model_string(self, model_str)
        elif train_set is not None:
            self._setup_train(train_set)

    # -- training ------------------------------------------------------------

    def _setup_train(self, train_set: Dataset) -> None:
        from .boosting import create_boosting
        from .parallel.comm import init_distributed
        # reference ordering: Network::Init precedes LoadData
        # (application.cpp:167-178) so distributed bin finding sees the mesh
        init_distributed(self.config)
        train_set.params.update(self.params)
        train_set.construct(self.config)
        cd = train_set.constructed
        # set-up's other whole; GBDT's constructor tiles it with its stages
        with obs.setup_span("booster.init"):
            self._gbdt = create_boosting(self.config, cd)
        # the booster may normalize config fields to their EFFECTIVE values
        # during construction (tpu_residency=stream forces
        # tpu_row_compact=false) — adopt them so the checkpoint fingerprint
        # covers what actually trains, and a streamed run resumes into a
        # device-resident one with matching math
        self.config = self._gbdt.config
        self.train_dataset = train_set
        self.feature_names = cd.feature_names
        self.num_total_features = cd.num_total_features
        self.mappers = cd.mappers
        self._real_feature_idx = cd.real_feature_idx
        self.num_model_per_iteration = self._gbdt.num_models
        self.pandas_categorical = getattr(train_set, "pandas_categorical", None)

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct(self.config)
        if data.reference is None or data._binned_aligned is None:
            Log.fatal("Add valid data failed: valid set must reference the training set")
        # every failure mode is checked BEFORE any booster mutation — a
        # caught error must not leave a half-attached valid set behind
        if any(nm == name for _ds, nm in self._valid_registry):
            Log.fatal("A validation set named %r is already attached; "
                      "names must be unique per booster", name)
        self._ensure_finalized()
        if self.trees and data.raw_data is None:
            Log.fatal("add_valid after training needs the valid set's "
                      "raw data to replay the forest — construct it "
                      "with free_raw_data=False")
        valid_raw = None
        if getattr(self.config, "linear_tree", False):
            # linear-leaf score updates need raw values for the valid rows
            if data.raw_data is None:
                Log.fatal("linear_tree=true: add_valid needs the valid "
                          "set's raw data (construct it with "
                          "free_raw_data=False)")
            from .dataset import extract_raw_slice
            cd = self.train_dataset.constructed
            valid_raw = extract_raw_slice(
                data.raw_data, [int(r) for r in cd.real_feature_idx],
                data.raw_data.shape[0])
        self._gbdt.add_valid(name, data._binned_aligned, data._metadata,
                             raw=valid_raw)
        self._valid_registry.append((data, name))
        # replay the already-trained forest into the new valid score (the
        # reference's AddValidDataset replays iter_ trees; without this,
        # eval on late-attached data would score the INITIAL model). The
        # fresh seed holds init_score_value which the finalized trees also
        # carry (bias folded into tree 0) — subtract it before adding.
        if self.trees:
            gbdt = self._gbdt
            K = max(self.num_model_per_iteration, 1)
            raw = np.asarray(self.predict(
                data.raw_data, raw_score=True,
                num_iteration=len(self.trees) // K), np.float32)
            raw = raw.T if raw.ndim == 2 else raw.reshape(1, -1)
            vs = gbdt.valid_sets[-1]
            vs.score = (vs.score - np.float32(gbdt.init_score_value)
                        + gbdt._put(raw.reshape(K, vs.num_data)))
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """Reference LGBM_BoosterResetParameter (c_api.cpp) — used by the
        reset_parameter callback for per-iteration schedules."""
        self.params.update(params)
        self.config = Config.from_params(self.params)
        if self._gbdt is not None:
            self._gbdt.reset_config(self.config)
        return self

    def rollback_one_iter(self) -> "Booster":
        """Reference GBDT::RollbackOneIter via LGBM_BoosterRollbackOneIter."""
        if self._gbdt is not None:
            self._gbdt.rollback_one_iter()
        return self

    def update(self, train_set=None, fobj=None) -> bool:
        """One boosting iteration (reference LGBM_BoosterUpdateOneIter /
        LGBM_BoosterUpdateOneIterCustom for user gradients).

        ``train_set`` swaps the training data under the existing model
        (reference LGBM_BoosterResetTrainingData, c_api.cpp): the new data's
        scores are seeded with the current forest's raw predictions.
        """
        if train_set is not None and train_set is not getattr(
                self, "train_dataset", None):
            if self._gbdt is not None:
                self._finalize()
            prev = list(self.trees)
            # capture before construct(): free_raw_data nulls raw_data
            X_new = train_set.raw_data
            if prev and X_new is None:
                Log.fatal("update(train_set=...) on a trained booster needs "
                          "the new Dataset's raw data to seed scores — "
                          "construct it with free_raw_data=False")
            self._setup_train(train_set)
            if prev:
                gbdt = self._gbdt
                # seed from model predictions ONLY: drop the fresh
                # boost-from-average bias (reference BoostFromAverage applies
                # only to an empty model, gbdt.cpp:357-377)
                if abs(gbdt.init_score_value) > 1e-15:
                    gbdt.score = gbdt.score - gbdt.init_score_value
                    for _vs in gbdt.valid_sets:
                        _vs.score = _vs.score - gbdt.init_score_value
                    gbdt.init_score_value = 0.0
                K = max(self.num_model_per_iteration, 1)
                raw = np.asarray(self.predict(X_new, raw_score=True,
                                              num_iteration=len(prev) // K))
                raw = raw.T if raw.ndim == 2 else raw
                gbdt.add_base_score(raw)
                self._prev_trees = prev
        if self._gbdt is None:
            Log.fatal("Booster has no training data: it was freed (train() "
                      "without keep_training_booster=True) — pass train_set "
                      "to update() to attach data")
        if fobj is not None:
            self._gbdt.train_one_iter_custom(fobj)
        else:
            self._gbdt.train_one_iter()
        return False

    def free_dataset(self) -> "Booster":
        """Release device-side training state (reference basic.py
        free_dataset): the booster stays usable for predict/save/load but
        cannot continue training without a new train_set."""
        self._gbdt = None
        if hasattr(self, "train_dataset"):
            del self.train_dataset
        return self

    def _ensure_finalized(self):
        """Materialize host trees iff device state changed since the last
        sync (shared by get_leaf_output, the C API's lazy sync, predict, and
        eval-time replay; one home for the K/prev-trees accounting). The
        mutation counter — not just the length — decides: rollback (explicit
        or the no-splits pop) followed by a retrain lands back on the same
        length with different trees."""
        if self._gbdt is None:
            return
        K = max(self.num_model_per_iteration, 1)
        expected = (len(getattr(self, "_prev_trees", []))
                    + self._gbdt.iter_ * K)
        synced = getattr(self, "_synced_mutations", -1)
        if len(self.trees) != expected or \
                getattr(self._gbdt, "mutations_", 0) != synced:
            self._finalize()

    def _finalize(self):
        forest = self._gbdt.finalize_model()
        self.trees = getattr(self, "_prev_trees", []) + \
            [t for it_trees in forest for t in it_trees]
        self._forest_rev = getattr(self, "_forest_rev", 0) + 1
        self._synced_mutations = getattr(self._gbdt, "mutations_", 0)
        self.init_score_value = self._gbdt.init_score_value
        self.best_iteration = getattr(self._gbdt, "best_iteration", 0)

    # -- checkpoint/resume (robustness/checkpoint.py; docs/Fault-Tolerance.md)

    def save_checkpoint(self, directory: Optional[str] = None) -> Optional[str]:
        """Write one atomic snapshot of the full training state — finalized
        forest, raw scores, bagging RNG key, iteration counter, eval history,
        config fingerprint — to ``directory`` (default: config
        ``checkpoint_dir``). Resumable via :meth:`resume` or
        ``engine.train(resume_from=...)``. Under multi-host execution every
        process participates in the (collective) state fetch but only
        process 0 writes; returns the written path, or None on non-writing
        ranks."""
        from .robustness.checkpoint import (CheckpointManager,
                                            config_fingerprint,
                                            fingerprinted_config)
        if self._gbdt is None:
            Log.fatal("save_checkpoint needs live training state — the "
                      "booster was freed or loaded from a model file")
        if self.config.boosting_normalized == "dart":
            Log.fatal("checkpoint/resume does not support boosting=dart "
                      "(host-side drop state is not captured)")
        directory = directory or self.config.checkpoint_dir
        mgr = CheckpointManager(directory,
                                keep_last_n=self.config.checkpoint_keep_last_n)
        self._ensure_finalized()
        state = self._gbdt.checkpoint_state()
        payload = {
            "config_fingerprint": config_fingerprint(self.config),
            "config": fingerprinted_config(self.config),
            "iteration": state["iter"],
            "state": state,
            "eval_history": self.eval_history,
            "booster": {
                "trees": self.trees,
                "prev_trees": list(getattr(self, "_prev_trees", [])),
                "best_iteration": self.best_iteration,
                "best_score": self.best_score,
                "feature_names": self.feature_names,
            },
        }
        from .robustness import distributed as _dist
        gang = _dist.gang_env()
        if gang is not None:
            # gang-consistent protocol: EVERY rank writes its shard, rank 0
            # commits the epoch manifest behind the commit barrier
            # (robustness/distributed.py; docs/Fault-Tolerance.md)
            client, rank, world = gang
            coord = _dist.GangCheckpointCoordinator(
                directory, client=client, rank=rank, world=world,
                keep_last_n=self.config.checkpoint_keep_last_n,
                elastic=self.config.elastic)
            path = coord.save(payload)
            Log.info("gang checkpoint shard written: %s (rank %d/%d, "
                     "iteration %d, %d trees)", path, rank, world,
                     state["iter"], len(self.trees))
            return path
        import jax
        if jax.process_count() > 1 and jax.process_index() != 0:
            return None
        path = mgr.save(payload)
        Log.info("checkpoint written: %s (iteration %d, %d trees)", path,
                 state["iter"], len(self.trees))
        return path

    def resume(self, path_or_dir: Optional[str] = None) -> "Booster":
        """Replay a checkpoint into this booster's live training state.

        ``path_or_dir`` is a snapshot file or a checkpoint directory (whose
        latest snapshot is used); default is config ``checkpoint_dir``. The
        booster must already be constructed against the SAME dataset and
        training config — a config-fingerprint mismatch fails loudly naming
        the differing fields. Continued training after resume is
        bit-identical to a run that was never interrupted."""
        from .robustness.checkpoint import (CheckpointError,
                                            CheckpointManager,
                                            config_fingerprint,
                                            config_mismatch_fields)
        if self._gbdt is None:
            Log.fatal("resume needs a constructed training setup — build "
                      "the Booster with the same train_set/params first")
        if self.config.boosting_normalized == "dart":
            Log.fatal("checkpoint/resume does not support boosting=dart "
                      "(host-side drop state is not captured)")
        target = path_or_dir or self.config.checkpoint_dir
        if not target:
            Log.fatal("resume: no checkpoint path given and checkpoint_dir "
                      "is empty")
        payload = CheckpointManager.load(target)
        if payload["config_fingerprint"] != config_fingerprint(self.config):
            fields = config_mismatch_fields(payload["config"], self.config)
            raise CheckpointError(
                f"config fingerprint mismatch resuming from {target}: the "
                f"snapshot was written under a config whose training "
                f"semantics differ in: {', '.join(fields) or '<unknown>'}. "
                f"Resume requires an identical training config (run-control "
                f"fields like num_iterations and paths are exempt).")
        self._gbdt.restore_checkpoint_state(payload["state"])
        b = payload.get("booster", {})
        self.trees = list(b.get("trees", []))
        self._prev_trees = list(b.get("prev_trees", []))
        self._forest_rev = getattr(self, "_forest_rev", 0) + 1
        self._synced_mutations = getattr(self._gbdt, "mutations_", 0)
        self.best_iteration = int(b.get("best_iteration", 0))
        self.best_score = b.get("best_score", {}) or {}
        self.eval_history = payload.get("eval_history", {}) or {}
        self.init_score_value = self._gbdt.init_score_value
        Log.info("resumed from checkpoint (id %s) at iteration %d "
                 "(%d trees)", payload.get("checkpoint_id", "?"),
                 self._gbdt.iter_, len(self.trees))
        return self

    # -- prediction ----------------------------------------------------------

    def num_trees(self) -> int:
        return len(self.trees)

    def current_iteration(self) -> int:
        return len(self.trees) // max(self.num_model_per_iteration, 1)

    def predict(self, data, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        self._ensure_finalized()
        if hasattr(data, "values") and hasattr(data, "columns"):
            data, _, _, _ = _data_from_pandas(data, self.pandas_categorical)
        if _is_sparse(data):
            # chunked densify bounds peak memory; tree traversal is
            # vectorized over dense rows (reference Predictor handles CSR
            # rows natively, predictor.hpp:25-241)
            csr = data.tocsr()
            chunk = max(1, (1 << 24) // max(csr.shape[1], 1))
            if csr.shape[0] > chunk:
                parts = [self.predict(csr[i:i + chunk], num_iteration=num_iteration,
                                      raw_score=raw_score, pred_leaf=pred_leaf,
                                      pred_contrib=pred_contrib, **kwargs)
                         for i in range(0, csr.shape[0], chunk)]
                return np.concatenate(parts, axis=0)
            data = csr.toarray()
        X = np.asarray(data, dtype=np.float64)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        K = max(self.num_model_per_iteration, 1)
        if num_iteration is None or num_iteration <= 0:
            num_iteration = self.best_iteration if self.best_iteration > 0 else \
                len(self.trees) // K
        use_trees = self.trees[: num_iteration * K]

        if pred_leaf:
            out = np.stack([t.predict_leaf(X) for t in use_trees], axis=1)
            return out
        if pred_contrib:
            if any(t.is_linear for t in use_trees):
                # TreeSHAP walks constant leaf outputs; attributing a
                # per-leaf linear model needs interventional SHAP over the
                # coefficients — fail loudly rather than return constants
                # that ignore the linear terms
                Log.fatal("pred_contrib is not supported for linear-tree "
                          "models (linear_tree=true): TreeSHAP "
                          "contributions are defined over constant leaf "
                          "outputs")
            # TreeSHAP contributions, [N, (F+1)*K] like the reference python
            # package (basic.py predict pred_contrib; tree.h:340 PredictContrib)
            F1 = self.num_total_features + 1
            out = np.zeros((K, X.shape[0], F1))
            for i, t in enumerate(use_trees):
                out[i % K] += t.predict_contrib(X, self.num_total_features)
            if self.config.boosting_normalized == "rf":
                out /= max(len(use_trees) // K, 1)   # rf averages tree outputs
            return out[0] if K == 1 else np.concatenate(
                [out[k] for k in range(K)], axis=1)

        N = X.shape[0]
        raw = np.zeros((K, N), dtype=np.float64)
        early_stop = bool(kwargs.get("pred_early_stop",
                                     self.config.pred_early_stop))
        if early_stop:
            from .objectives import OBJECTIVE_ALIASES
            obj = OBJECTIVE_ALIASES.get(self.config.objective, self.config.objective)
            if obj not in ("binary", "multiclass", "multiclassova"):
                # reference prediction_early_stop.cpp: binary/multiclass only
                Log.fatal("Early stopping prediction is only supported for "
                          "binary and multiclass objectives")
        if early_stop and not raw_score and K >= 1 and len(use_trees):
            # margin-based per-row early stop (prediction_early_stop.cpp:
            # binary |raw| margin, multiclass top1-top2 margin)
            freq = max(int(kwargs.get("pred_early_stop_freq",
                                      self.config.pred_early_stop_freq)), 1)
            margin_thr = float(kwargs.get("pred_early_stop_margin",
                                          self.config.pred_early_stop_margin))
            n_iter_used = len(use_trees) // K
            active = np.ones(N, dtype=bool)
            for it in range(n_iter_used):
                rows = np.nonzero(active)[0]
                if len(rows) == 0:
                    break
                for k in range(K):
                    raw[k, rows] += use_trees[it * K + k].predict(X[rows])
                if (it + 1) % freq == 0:
                    if K == 1:
                        # reference CreateBinary margin = 2*|raw|
                        # (prediction_early_stop.cpp)
                        margin = 2.0 * np.abs(raw[0, rows])
                    else:
                        part = np.sort(raw[:, rows], axis=0)
                        margin = part[-1] - part[-2]
                    active[rows] = margin < margin_thr
        else:
            # large batches route through the device-side stacked-forest
            # evaluator (integer rank-exact traversal; the analog of the
            # reference's OMP row-parallel Predictor, predictor.hpp:25-241);
            # categorical splits stay on the host path
            device_ok = (N * max(len(use_trees), 1) >= 1_000_000
                         and not kwargs.get("force_host_predict", False))
            forests = None
            if device_ok:
                forests = self._stacked_forests(use_trees, K)
                device_ok = forests is not None
            if device_ok:
                from .ops.predict import forest_predict_raw
                for k in range(K):
                    raw[k] = forest_predict_raw(
                        use_trees[k::K], X, self.num_total_features,
                        forest=forests[k])
            else:
                for i, t in enumerate(use_trees):
                    raw[i % K] += t.predict(X)
        if self.config.boosting_normalized == "rf":
            # average of already-converted tree outputs (rf.hpp average_output_)
            raw /= max(len(use_trees) // K, 1)
        elif not raw_score:
            raw = self._convert_output(raw)
        return raw[0] if K == 1 else raw.T

    def _stacked_forests(self, use_trees, K: int):
        """Per-class StackedForests for device batch predict, cached across
        calls in a small LRU keyed by the tree slice — serving loops that
        alternate num_iteration (full model vs early-stopped prefix) keep
        both entries warm instead of rebuilding every call. Returns None
        when any class slice holds a categorical split — the host path
        handles those."""
        from .ops.predict import StackedForest
        from .utils.cache import LRUCache
        # _forest_rev (not len(trees)) keys the content: rollback + retrain
        # lands back on the same length with different trees
        key = (getattr(self, "_forest_rev", 0), len(use_trees), K)
        cache = getattr(self, "_stacked_cache", None)
        if cache is None:
            cache = self._stacked_cache = LRUCache(capacity=4)
        forests = cache.get(key, default=False)
        if forests is not False:
            return forests
        if any((np.asarray(t.decision_type) & 1).any() for t in use_trees):
            forests = None                   # cheap pre-scan: host path
        else:
            forests = [StackedForest(use_trees[k::K], self.num_total_features)
                       for k in range(K)]
        cache.put(key, forests)
        return forests

    def _convert_output(self, raw: np.ndarray) -> np.ndarray:
        obj = self.config.objective
        from .objectives import OBJECTIVE_ALIASES
        name = OBJECTIVE_ALIASES.get(obj, obj)
        if name == "binary":
            return 1.0 / (1.0 + np.exp(-self.config.sigmoid * raw))
        if name == "multiclass":
            e = np.exp(raw - raw.max(axis=0, keepdims=True))
            return e / e.sum(axis=0, keepdims=True)
        if name == "multiclassova":
            return 1.0 / (1.0 + np.exp(-self.config.sigmoid * raw))
        if name == "poisson":
            return np.exp(raw)
        if name == "xentropy":
            return 1.0 / (1.0 + np.exp(-raw))
        if name == "xentlambda":
            return np.log1p(np.exp(raw))
        return raw

    # -- evaluation ----------------------------------------------------------

    def _feval_results(self, feval, dataset_name):
        """Run a custom eval callable for one attached dataset (reference
        __inner_eval's feval leg, basic.py:1612-1620)."""
        if feval is None:
            return []
        out = []
        if dataset_name == self._train_data_name:
            train_ds = getattr(self, "train_dataset", None)
            if train_ds is None:
                Log.fatal("eval_train with a custom feval needs the "
                          "training Dataset, which free_dataset() released")
            preds = self._gbdt._fetch(self._gbdt._convert(self._gbdt.score))[
                :, self._gbdt._real_rows()].reshape(-1)
            res = feval(preds, train_ds)
            res = [res] if isinstance(res, tuple) else res
            out.extend((dataset_name, n, v, h) for n, v, h in res)
            return out
        for vs in self._gbdt.valid_sets:
            if vs.name == dataset_name:
                preds = self._gbdt._fetch(
                    self._gbdt._convert(vs.score)).reshape(-1)
                res = feval(preds, vs)
                res = [res] if isinstance(res, tuple) else res
                out.extend((dataset_name, n, v, h) for n, v, h in res)
        return out

    def eval(self, data, name, feval=None):
        """Evaluate the current model on `data` (reference basic.py:1543):
        the training set, an attached valid set, or a new Dataset (which is
        attached as a valid set first, like the reference's push)."""
        if not isinstance(data, Dataset):
            raise TypeError("Can only eval for Dataset instance")
        if data is getattr(self, "train_dataset", None):
            return self.eval_train(feval)
        for ds, nm in self._valid_registry:
            if data is ds:
                return (self._gbdt.eval_all(only=nm)
                        + self._feval_results(feval, nm))
        self.add_valid(data, name)
        return (self._gbdt.eval_all(only=name)
                + self._feval_results(feval, name))

    def eval_train(self, feval=None):
        """Evaluate on the training data (reference basic.py:1577)."""
        res = [(self._train_data_name, n, v, h)
               for d, n, v, h in self._gbdt.eval_all(force_training=True,
                                                     only="training")]
        return res + self._feval_results(feval, self._train_data_name)

    def eval_valid(self, feval=None):
        """Evaluate on every attached validation set (basic.py:1592)."""
        names = [nm for _ds, nm in self._valid_registry] or             [vs.name for vs in self._gbdt.valid_sets]
        res = [r for r in self._gbdt.eval_all() if r[0] != "training"]
        if feval is not None:
            for nm in names:
                res.extend(self._feval_results(feval, nm))
        return res

    def set_train_data_name(self, name: str) -> "Booster":
        """Display name of the training data in eval output
        (reference basic.py:1400)."""
        self._train_data_name = name
        return self

    # -- attributes (reference basic.py:1932-1969: in-memory k/v store) ------

    def attr(self, key: str):
        return self._attr.get(key)

    def set_attr(self, **kwargs) -> "Booster":
        for k, v in kwargs.items():
            if v is None:
                self._attr.pop(k, None)
            else:
                self._attr[k] = str(v)
        return self

    # -- network (reference basic.py:1374-1399) ------------------------------

    def set_network(self, machines, local_listen_port: int = 12400,
                    listen_time_out: int = 120,
                    num_machines: int = 1) -> "Booster":
        """Record the distributed wiring params (reference SetNetwork).
        Here the mesh is wired when training starts (jax.distributed),
        so calling this after a booster has trained only affects the
        next training setup."""
        if not isinstance(machines, str):
            machines = ",".join(machines)
        self.params.update(machines=machines,
                           local_listen_port=local_listen_port,
                           time_out=listen_time_out,
                           num_machines=num_machines)
        self.config = Config.from_params(self.params)
        if self._gbdt is not None:
            Log.warning("set_network after training setup applies to the "
                        "next training, not the current booster")
        return self

    def free_network(self) -> "Booster":
        for k in ("machines", "local_listen_port", "time_out",
                  "num_machines"):
            self.params.pop(k, None)
        self.config = Config.from_params(self.params)
        return self

    # -- model io ------------------------------------------------------------

    def save_model(self, filename: str, num_iteration: Optional[int] = None) -> "Booster":
        from .io.model_text import save_model_file
        save_model_file(self, filename, num_iteration)
        return self

    def model_to_string(self, num_iteration: Optional[int] = None) -> str:
        from .io.model_text import model_to_string
        return model_to_string(self, num_iteration)

    def dump_model(self, num_iteration: Optional[int] = None) -> Dict:
        from .io.model_json import dump_model_dict
        return dump_model_dict(self, num_iteration)

    # -- introspection -------------------------------------------------------

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        """split counts or total gains per feature (reference boosting.h:216)."""
        imp = np.zeros(self.num_total_features, dtype=np.float64)
        for t in self.trees:
            for i in range(t.num_internal):
                if importance_type == "split":
                    imp[t.split_feature[i]] += 1
                else:
                    imp[t.split_feature[i]] += t.split_gain[i]
        if importance_type == "split":
            return imp.astype(np.int64)
        return imp

    def feature_name(self) -> List[str]:
        return list(self.feature_names)

    def num_feature(self) -> int:
        """Number of (raw) features the model was trained on
        (reference basic.py:1775 / LGBM_BoosterGetNumFeature)."""
        return int(self.num_total_features)

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """Output value of one leaf (reference basic.py:1746 /
        LGBM_BoosterGetLeafValue)."""
        self._ensure_finalized()
        return float(self.trees[tree_id].leaf_value[leaf_id])

    def __getstate__(self):
        state = self.__dict__.copy()
        state.pop("_gbdt", None)
        state.pop("train_dataset", None)
        # registry holds live Datasets (whose .reference is the training
        # set) — stale after unpickling anyway since _gbdt is dropped
        state["_valid_registry"] = []
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._gbdt = None
