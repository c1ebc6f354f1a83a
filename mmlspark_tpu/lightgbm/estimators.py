"""LightGBMClassifier / LightGBMRegressor / LightGBMRanker pipeline stages.

API parity with reference ``lightgbm/LightGBMClassifier.scala:26-208``,
``LightGBMRegressor.scala``, ``LightGBMRanker.scala:80-110``,
``LightGBMBase.scala:24-293`` (batch training with model continuation,
validation early stopping, native-model export). The training engine is the
jitted XLA tree grower in ``engine.py``/``trainer.py``.
"""

from __future__ import annotations

import numpy as np

from ..core import Estimator, Model, Param, TypeConverters as TC
from ..core.contracts import (HasGroupCol, HasProbabilityCol,
                              HasRawPredictionCol)
from ..core.utils import as_2d_features
from .booster import Booster
from .params import LightGBMSharedParams
from .ranker_objective import (build_group_index, make_lambdarank_grad_hess,
                               ndcg_at_k)
from .trainer import TrainConfig, TrainResult, train


def extract_features(df, col: str, sparse_feature_count: int = 0):
    """Features from a DataFrame: the framework's padded-COO pair
    (``<col>_indices``/``<col>_values``, e.g. the VW featurizer's output)
    becomes a ``SparseData`` feeding the CSR-equivalent engine (reference
    ``TrainUtils.scala:33-92``); otherwise a dense [n, F] matrix."""
    from .sparse import SparseData, coalesce_coo
    icol, vcol = f"{col}_indices", f"{col}_values"
    if icol in df.columns and vcol in df.columns:
        idx = np.asarray(df[icol], np.int32)
        val = np.asarray(df[vcol], np.float32)
        # engine invariant: unique indices per row (sumCollisions=False
        # featurizer output may carry duplicates — merge them)
        idx, val = coalesce_coo(idx, val)
        # empty input / all-padding rows: keep F >= 1 so the binning
        # scratch shapes stay valid (the sparse analogue of the dense
        # path's tolerance for empty partitions)
        max_idx = int(idx.max()) if idx.size else -1
        F = max(sparse_feature_count, max_idx + 1, 1)
        return SparseData(idx, val, F)
    return as_2d_features(df, col)


class _LightGBMBase(Estimator, LightGBMSharedParams):
    """Template-method base (reference ``LightGBMBase.train``):
    batching → data extraction → objective config → engine train → model."""

    def _objective_config(self, y: np.ndarray) -> dict:
        raise NotImplementedError

    def _make_model(self, booster: Booster, result: TrainResult) -> Model:
        raise NotImplementedError

    def _grad_override(self, df, y):
        return None

    def _valid_eval_fn(self, valid_df):
        return None

    def _preprocess(self, df):
        return df

    def _categorical_slots(self, df) -> tuple:
        """Resolve categoricalSlotIndexes/Names to slot indexes
        (reference: names resolve through ML attribute metadata,
        ``LightGBMBase.scala``; here through slotNames or the features
        column's metadata)."""
        idx = list(self.getCategoricalSlotIndexes() or [])
        names = self.getCategoricalSlotNames() or []
        if names:
            slots = self.getSlotNames() or []
            if not slots:
                from ..core import ColumnMetadata
                meta = ColumnMetadata.get(df, self.getFeaturesCol()) or {}
                slots = meta.get("slot_names", [])
            if not slots:
                raise ValueError(
                    "categoricalSlotNames given but no slot names are "
                    "available: set slotNames (or attach 'slot_names' "
                    "column metadata), or use categoricalSlotIndexes")
            missing = [nm for nm in names if nm not in slots]
            if missing:
                raise ValueError(
                    f"categoricalSlotNames not found in slotNames: "
                    f"{missing}")
            idx.extend(slots.index(nm) for nm in names)
        return tuple(sorted(set(int(i) for i in idx)))

    def _fit(self, df):
        df = self._preprocess(df)
        # resolve name->slot via metadata BEFORE partitioning: derived
        # frames carry metadata, but resolving once here also covers
        # callers that hand-build partitions
        cat_slots = self._categorical_slots(df)
        num_batches = self.getNumBatches()
        if num_batches and num_batches > 1:
            parts = df.repartition(num_batches).partitions()
        else:
            parts = [df]
        return self._fit_batches(parts, cat_slots)

    def _fit_batches(self, batches, cat_slots=None):
        """The ONE continuation loop behind both ``numBatches`` and
        ``fit_stream``: warm start from ``modelString``, then each batch
        continues the previous batch's booster."""
        booster: Booster | None = None
        if self.getModelString():
            booster = Booster.load_native(self.getModelString())
        result = None
        for batch in batches:
            if cat_slots is None:
                cat_slots = self._categorical_slots(batch)
            result = self._fit_batch(batch, init_booster=booster,
                                     cat_slots=cat_slots)
            booster = result.booster
        if result is None:
            raise ValueError("received an empty batch stream")
        model = self._make_model(booster, result)
        self._copy_params_to(model)
        return model

    def fit_stream(self, batches):
        """Out-of-core training: consume an iterable of DataFrames (e.g.
        ``io.parquet.stream_parquet``) one at a time with booster
        continuation — the same per-batch loop as ``numBatches``
        (reference ``LightGBMBase`` batch training /
        ``BinaryFileFormat.scala:34-110``'s unbounded-source role), but
        memory-bounded by the largest batch instead of the dataset.
        Each batch must carry the same columns; categorical slots
        resolve from the first batch's metadata."""
        model = self._fit_batches(self._preprocess(b) for b in batches)
        model._resolve_parent(self)
        return model

    def _fit_batch(self, df, init_booster: Booster | None,
                   cat_slots: tuple | None = None) -> TrainResult:
        from .sparse import SparseData

        # ---- split validation rows (reference validationIndicatorCol)
        valid = None
        valid_eval_fn = None
        valid_init_scores = None
        train_df = df
        valid_df = None
        if self.isSet("validationIndicatorCol"):
            flag = np.asarray(df[self.getValidationIndicatorCol()],
                              dtype=bool)
            train_df = df.filter(~flag)
            valid_df = df.filter(flag)

        fcol = self.getFeaturesCol()
        x = extract_features(train_df, fcol, self.getSparseFeatureCount())
        sparse = isinstance(x, SparseData)
        if valid_df is not None:
            xv = extract_features(
                valid_df, fcol,
                x.num_features if sparse else 0)
            yv = np.asarray(valid_df[self.getLabelCol()], np.float32)
            wv = (np.asarray(valid_df[self.getWeightCol()], np.float32)
                  if self.isSet("weightCol") else None)
            valid = (xv, yv, wv)
            valid_eval_fn = self._valid_eval_fn(valid_df)
            if self.isSet("initScoreCol"):
                valid_init_scores = np.asarray(
                    valid_df[self.getInitScoreCol()], np.float32)

        y = np.asarray(train_df[self.getLabelCol()], np.float32)
        w = (np.asarray(train_df[self.getWeightCol()], np.float32)
             if self.isSet("weightCol") else None)
        init_scores = (np.asarray(train_df[self.getInitScoreCol()],
                                  np.float32)
                       if self.isSet("initScoreCol") else None)

        if cat_slots is None:
            cat_slots = self._categorical_slots(df)
        cfg = TrainConfig(**self._train_config_kwargs(),
                          categorical_features=cat_slots,
                          **self._objective_config(y))
        names = self.getSlotNames() or (
            None if sparse else
            [f"Column_{i}" for i in range(x.shape[1])])
        n_rows = x.n_rows if sparse else x.shape[0]
        mesh = self._training_mesh(n_rows)
        axes = self._shard_axes()
        return train(x, y, w, cfg, valid=valid, init_booster=init_booster,
                     init_scores=init_scores,
                     valid_init_scores=valid_init_scores,
                     feature_names=names,
                     grad_hess_override=self._grad_override(train_df, y),
                     valid_eval_fn=valid_eval_fn, mesh=mesh,
                     mesh_axis=axes if len(axes) > 1 else axes[0])

    def _shard_axes(self) -> tuple:
        """``shardAxisName`` parsed: comma-separated names declare a
        HIERARCHICAL mesh (e.g. ``"slice,dp"`` — rows shard over the
        product, the histogram psum composes DCN across slices with ICI
        within them)."""
        axes = tuple(a.strip() for a in
                     self.getShardAxisName().split(",") if a.strip())
        if not axes:
            raise ValueError(
                "shardAxisName must name at least one mesh axis "
                f"(got {self.getShardAxisName()!r})")
        return axes

    def _training_mesh(self, n_rows: int):
        """Device mesh for distributed histogram training.

        The reference sizes its worker set from cluster topology
        (``ClusterUtil.getNumTasksPerExecutor``, ``LightGBMBase.scala:
        102-138``); here the "cluster" is the visible device set.
        numShards: 0 = auto (all devices when the data is big enough to be
        worth the collective), 1 = single device, N = exactly N devices.
        """
        import jax
        from jax.sharding import Mesh

        ns = self.getNumShards()
        devices = jax.devices()
        if ns == 0:
            ns = len(devices) if n_rows >= 4096 and len(devices) > 1 else 1
        if ns > len(devices):
            raise ValueError(
                f"numShards={ns} asks for more devices than exist "
                f"({len(devices)}); training on fewer shards than asked "
                "for is never silent")
        if ns <= 1:
            return None
        axes = self._shard_axes()
        if len(axes) == 1:
            return Mesh(np.asarray(devices[:ns]), axes)
        if len(axes) != 2:
            raise ValueError(
                f"shardAxisName supports one or two levels, got {axes}")
        # hierarchical (DCN x ICI): group devices by their slice when
        # the platform exposes one (TPU pods set slice_index); hosts
        # with a single slice still get the two-level mesh shape so the
        # composed psum compiles identically
        groups: dict = {}
        for d in devices[:ns]:
            groups.setdefault(getattr(d, "slice_index", 0), []).append(d)
        sizes = {len(g) for g in groups.values()}
        if len(groups) > 1 and len(sizes) == 1:
            arr = np.asarray([g for g in groups.values()])
        else:
            arr = np.asarray(devices[:ns]).reshape(1, -1)
        return Mesh(arr, axes)


class _BoosterModelMixin:
    """Shared model surface: native export, importances, SHAP, leaves."""

    leafPredictionCol = Param("leafPredictionCol",
                              "output column with per-tree leaf indices",
                              TC.toString)
    featuresShapCol = Param("featuresShapCol",
                            "output column with SHAP contributions",
                            TC.toString)
    numIterationsForPrediction = Param(
        "numIterationsForPrediction",
        "use only the first k iterations when predicting (0 = all/best)",
        TC.toInt, default=0)
    startIteration = Param(
        "startIteration",
        "skip the first k iterations when predicting (reference "
        "setStartIteration)", TC.toInt, default=0)

    booster: Booster

    def get_booster(self) -> Booster:
        return self.booster

    def save_native_model(self, path: str) -> None:
        """Reference ``saveNativeModel`` — LightGBM text model format."""
        with open(path, "w") as f:
            f.write(self.booster.save_native())

    saveNativeModel = save_native_model

    def get_native_model_string(self) -> str:
        return self.booster.save_native()

    def get_feature_importances(self, importance_type: str = "split"):
        return self.booster.feature_importances(importance_type).tolist()

    getFeatureImportances = get_feature_importances

    def _num_iter(self):
        k = self.getNumIterationsForPrediction()
        return k if k and k > 0 else None

    def _maybe_extra_outputs(self, df, x):
        out = df
        start = self.get("startIteration")
        if self.isSet("leafPredictionCol"):
            leaves = self.booster.predict_leaf(x, self._num_iter(),
                                               start_iteration=start)
            out = out.with_column(self.getLeafPredictionCol(),
                                  leaves.astype(np.float64))
        if self.isSet("featuresShapCol"):
            from .shap import booster_shap_values
            from .sparse import SparseData
            if isinstance(x, SparseData):
                raise NotImplementedError(
                    "featuresShapCol on padded-COO sparse input is not "
                    "supported (a dense [n, F] SHAP matrix at 2^18 "
                    "features would defeat the sparse path) — densify a "
                    "feature subset first")
            shap = booster_shap_values(self.booster, x, x.shape[1],
                                       start_iteration=start,
                                       num_iteration=self._num_iter())
            out = out.with_column(self.getFeaturesShapCol(), shap)
        return out

    def _save_extra(self, path: str) -> None:
        import os
        # The text model is self-contained (init score folded into tree 0).
        with open(os.path.join(path, "model.txt"), "w") as f:
            f.write(self.booster.save_native())

    def _load_extra(self, path: str) -> None:
        import os
        with open(os.path.join(path, "model.txt")) as f:
            self.booster = Booster.load_native(f.read())


# ------------------------------------------------------------------ classifier
class LightGBMClassifier(_LightGBMBase, HasRawPredictionCol,
                         HasProbabilityCol):
    objective = Param("objective", "binary | multiclass | multiclassova",
                      TC.toString,
                      default="binary")
    isUnbalance = Param("isUnbalance", "auto-weight positive class",
                        TC.toBoolean, default=False)
    scalePosWeight = Param("scalePosWeight", "positive class weight",
                           TC.toFloat, default=1.0)
    sigmoid = Param("sigmoid", "sigmoid sharpness", TC.toFloat, default=1.0)
    numClass = Param("numClass", "class count (multiclass)", TC.toInt,
                     default=1)
    thresholds = Param("thresholds", "per-class prediction thresholds",
                       TC.toListFloat, default=[])

    def _objective_config(self, y):
        objective = self.getObjective()
        n_classes = int(y.max()) + 1 if y.size else 2
        if objective == "binary" and n_classes > 2:
            objective = "multiclass"
        num_class = max(self.getNumClass(),
                        n_classes if objective != "binary" else 1)
        return dict(objective=objective, num_class=num_class,
                    sigmoid=self.getSigmoid(),
                    is_unbalance=self.getIsUnbalance(),
                    scale_pos_weight=self.getScalePosWeight())

    def _make_model(self, booster, result):
        return LightGBMClassificationModel(booster=booster)


class LightGBMClassificationModel(_BoosterModelMixin, Model,
                                  LightGBMSharedParams, HasRawPredictionCol,
                                  HasProbabilityCol):
    thresholds = Param("thresholds", "per-class prediction thresholds",
                       TC.toListFloat, default=[])

    def __init__(self, booster: Booster | None = None, **kwargs):
        super().__init__(**kwargs)
        if booster is not None:
            self.booster = booster

    @property
    def numClasses(self) -> int:
        return max(self.booster.num_class, 2)

    def _transform(self, df):
        x = extract_features(df, self.getFeaturesCol(),
                             self.getSparseFeatureCount())
        raw = self.booster.raw_scores(
            x, self._num_iter(),
            start_iteration=self.get("startIteration"))
        prob = np.asarray(self.booster.transform_scores(raw))
        if raw.ndim == 1:  # binary: expand to 2-class columns
            raw2 = np.stack([-raw, raw], axis=1)
            prob2 = np.stack([1 - prob, prob], axis=1)
        else:
            raw2, prob2 = raw, prob
        thresholds = self.getThresholds()
        if thresholds:
            scaled = prob2 / np.asarray(thresholds)[None, :]
            pred = scaled.argmax(axis=1).astype(np.float64)
        else:
            pred = prob2.argmax(axis=1).astype(np.float64)
        out = (df.with_column(self.getRawPredictionCol(), raw2)
                 .with_column(self.getProbabilityCol(), prob2)
                 .with_column(self.getPredictionCol(), pred))
        return self._maybe_extra_outputs(out, x)

    @staticmethod
    def load_native_model_from_string(model_str: str,
                                      **kwargs) -> "LightGBMClassificationModel":
        return LightGBMClassificationModel(
            booster=Booster.load_native(model_str), **kwargs)

    @staticmethod
    def load_native_model_from_file(path: str,
                                    **kwargs) -> "LightGBMClassificationModel":
        with open(path) as f:
            return LightGBMClassificationModel.load_native_model_from_string(
                f.read(), **kwargs)

    loadNativeModelFromString = load_native_model_from_string
    loadNativeModelFromFile = load_native_model_from_file


# ------------------------------------------------------------------- regressor
class LightGBMRegressor(_LightGBMBase):
    objective = Param("objective",
                      "regression | regression_l1 | huber | fair | poisson | "
                      "quantile | mape | gamma | tweedie | cross_entropy | "
                      "cross_entropy_lambda", TC.toString,
                      default="regression")
    alpha = Param("alpha", "quantile level / huber delta", TC.toFloat,
                  default=0.9)
    fairC = Param("fairC", "fair-loss c", TC.toFloat, default=1.0)
    tweedieVariancePower = Param("tweedieVariancePower",
                                 "tweedie variance power in (1, 2)",
                                 TC.toFloat, default=1.5)

    def _objective_config(self, y):
        return dict(objective=self.getObjective(), alpha=self.getAlpha(),
                    fair_c=self.getFairC(),
                    tweedie_variance_power=self.getTweedieVariancePower())

    def _make_model(self, booster, result):
        return LightGBMRegressionModel(booster=booster)


class LightGBMRegressionModel(_BoosterModelMixin, Model,
                              LightGBMSharedParams):
    def __init__(self, booster: Booster | None = None, **kwargs):
        super().__init__(**kwargs)
        if booster is not None:
            self.booster = booster

    def _transform(self, df):
        x = extract_features(df, self.getFeaturesCol(),
                             self.getSparseFeatureCount())
        raw = self.booster.raw_scores(
            x, self._num_iter(),
            start_iteration=self.get("startIteration"))
        pred = np.asarray(self.booster.transform_scores(raw))
        out = df.with_column(self.getPredictionCol(), pred)
        return self._maybe_extra_outputs(out, x)

    @staticmethod
    def load_native_model_from_string(model_str: str, **kwargs):
        return LightGBMRegressionModel(
            booster=Booster.load_native(model_str), **kwargs)

    @staticmethod
    def load_native_model_from_file(path: str, **kwargs):
        with open(path) as f:
            return LightGBMRegressionModel.load_native_model_from_string(
                f.read(), **kwargs)

    loadNativeModelFromString = load_native_model_from_string
    loadNativeModelFromFile = load_native_model_from_file


# --------------------------------------------------------------------- ranker
class LightGBMRanker(_LightGBMBase, HasGroupCol):
    objective = Param("objective", "lambdarank", TC.toString,
                      default="lambdarank")
    maxPosition = Param("maxPosition", "NDCG truncation for eval", TC.toInt,
                        default=20)
    truncationLevel = Param("truncationLevel",
                            "lambdarank pair truncation level", TC.toInt,
                            default=30)
    evalAt = Param("evalAt", "NDCG@k eval positions", TC.toListInt,
                   default=[1, 3, 5, 10])
    repartitionByGroupingColumn = Param(
        "repartitionByGroupingColumn",
        "keep query groups contiguous (reference :92-101)", TC.toBoolean,
        default=True)

    def _preprocess(self, df):
        # Reference LightGBMRanker.preprocessData: sort within partitions by
        # group so each query's docs are contiguous.
        if self.getRepartitionByGroupingColumn():
            return df.sort(self.getGroupCol())
        return df

    def _objective_config(self, y):
        return dict(objective="lambdarank")

    def _grad_override(self, df, y):
        groups = _group_ids(df[self.getGroupCol()])
        gidx = build_group_index(groups)
        return make_lambdarank_grad_hess(
            np.asarray(y, np.float32), gidx,
            truncation_level=self.getTruncationLevel())

    def _valid_eval_fn(self, valid_df):
        vgroups = _group_ids(valid_df[self.getGroupCol()])
        k = self.getMaxPosition()

        def eval_ndcg(raw_scores, yv, wv):
            return ndcg_at_k(raw_scores, yv.astype(np.float64), vgroups, k=k)
        return eval_ndcg

    def _make_model(self, booster, result):
        return LightGBMRankerModel(booster=booster)

    def fit_stream(self, batches):
        """Streaming fit with a group-integrity guard: each batch must
        hold WHOLE query groups (the reference repartitions by the
        grouping column for exactly this reason,
        ``LightGBMRanker.scala:92-101``) — a group straddling two
        batches would train as two independent queries with corrupted
        pairwise gradients, so a group id reappearing in a later batch
        raises instead of silently mis-training."""
        gcol = self.getGroupCol()
        seen: set = set()

        def guarded():
            for batch in batches:
                gids = set(np.asarray(batch[gcol]).tolist())
                overlap = gids & seen
                if overlap:
                    raise ValueError(
                        f"query group(s) {sorted(overlap)[:5]} span "
                        "multiple stream batches; the ranker needs whole "
                        "groups per batch — repartition the stream by "
                        "the grouping column")
                seen.update(gids)
                yield batch
        return super().fit_stream(guarded())


class LightGBMRankerModel(_BoosterModelMixin, Model, LightGBMSharedParams,
                          HasGroupCol):
    def __init__(self, booster: Booster | None = None, **kwargs):
        super().__init__(**kwargs)
        if booster is not None:
            self.booster = booster

    def _transform(self, df):
        x = extract_features(df, self.getFeaturesCol(),
                             self.getSparseFeatureCount())
        raw = self.booster.raw_scores(
            x, self._num_iter(),
            start_iteration=self.get("startIteration"))
        out = df.with_column(self.getPredictionCol(), np.asarray(raw))
        return self._maybe_extra_outputs(out, x)

    def evaluate_ndcg(self, df, k: int = 10) -> float:
        scored = self.transform(df)
        return ndcg_at_k(np.asarray(scored[self.getPredictionCol()]),
                         np.asarray(scored[self.getLabelCol()], np.float64),
                         _group_ids(scored[self.getGroupCol()]), k=k)

    @staticmethod
    def load_native_model_from_string(model_str: str, **kwargs):
        return LightGBMRankerModel(
            booster=Booster.load_native(model_str), **kwargs)

    loadNativeModelFromString = load_native_model_from_string


def _group_ids(col: np.ndarray) -> np.ndarray:
    """Group column (int/string, reference supports both) → dense int ids."""
    _, ids = np.unique(np.asarray([str(v) for v in col.tolist()]),
                       return_inverse=True)
    return ids
