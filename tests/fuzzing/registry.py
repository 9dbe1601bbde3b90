"""TestObject registry: one entry per pipeline stage.

Parity: the reference's fuzzing backbone (core test
fuzzing/Fuzzing.scala:604-631) — every stage registers TestObjects that
drive serialization round-trips, fit/transform smoke runs and
getter/setter checks; a completeness test asserts no stage is missing
(FuzzingTest.scala:19-80).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.pipeline import Estimator, PipelineStage, Transformer

_rng = np.random.default_rng(7)


def _obj_col(values) -> np.ndarray:
    out = np.empty(len(values), dtype=object)
    for i, v in enumerate(values):
        out[i] = v
    return out


def _tabular(n=60):
    x1 = _rng.normal(size=n)
    x2 = _rng.normal(size=n)
    y = (x1 + 0.5 * x2 > 0).astype(np.float64)
    return DataFrame({
        "x1": x1, "x2": x2,
        "features": np.stack([x1, x2], axis=1),
        "label": y,
        "cat": np.asarray([("a", "b", "c")[i % 3] for i in range(n)],
                          dtype=object),
        "text": _obj_col([("good great fine", "bad awful poor")[i % 2]
                          for i in range(n)]),
    })


def _images(n=4):
    col = np.empty(n, dtype=object)
    for i in range(n):
        col[i] = _rng.uniform(0, 255, (12, 12, 3)).astype(np.float32)
    return DataFrame({"image": col, "label": np.asarray(
        [float(i % 2) for i in range(n)])})


def _interactions():
    users = np.repeat(np.arange(12), 5)
    items = np.concatenate([(np.arange(5) + (u % 2) * 5) for u in range(12)])
    return DataFrame({"user": users.astype(np.int64),
                      "item": items.astype(np.int64),
                      "rating": np.ones(len(users))})


@dataclass
class TestObject:
    """A stage instance + the dataset(s) to exercise it with."""

    __test__ = False  # dataclass, not a pytest collection target

    stage: PipelineStage
    fit_df: DataFrame
    transform_df: Optional[DataFrame] = None
    compare_cols: Optional[List[str]] = None   # None = all new columns
    skip_serialization: bool = False
    approx: float = 1e-6

    @property
    def df_for_transform(self) -> DataFrame:
        return self.transform_df if self.transform_df is not None \
            else self.fit_df


def _linear_model():
    class _Probe(Transformer):
        def _transform(self, df):
            # read named columns OR a features vector, whichever exists
            if "x1" in df:
                z = np.asarray(df.col("x1"), np.float64)
            else:
                z = np.asarray(df.col("features"), np.float64)[:, 0]
            p = 1 / (1 + np.exp(-z))
            return df.with_column("probability",
                                  np.stack([1 - p, p], axis=1))
    return _Probe()


def build_registry() -> Dict[str, TestObject]:
    """stage-class-name -> TestObject. Import inside so discovery sees
    every module."""
    from mmlspark_tpu.automl.search import FindBestModel, TuneHyperparameters
    from mmlspark_tpu.causal.diff_in_diff import (
        DiffInDiffEstimator, SyntheticControlEstimator,
        SyntheticDiffInDiffEstimator)
    from mmlspark_tpu.causal.dml import DoubleMLEstimator, ResidualTransformer
    from mmlspark_tpu.causal.ortho_forest import OrthoForestDMLEstimator
    from mmlspark_tpu.cyber.anomaly import (AccessAnomaly,
                                            ComplementAccessTransformer)
    from mmlspark_tpu.cyber.feature import (IdIndexer,
                                            PartitionedMinMaxScaler,
                                            PartitionedStandardScaler)
    from mmlspark_tpu.dl.text import DeepTextClassifier
    from mmlspark_tpu.dl.vision import DeepVisionClassifier
    from mmlspark_tpu.dl.causal_lm import CausalLM
    from mmlspark_tpu.dl.embedder import SentenceEmbedder
    from mmlspark_tpu.exploratory.balance import (AggregateBalanceMeasure,
                                                  DistributionBalanceMeasure,
                                                  FeatureBalanceMeasure)
    from mmlspark_tpu.explainers.ice import ICETransformer
    from mmlspark_tpu.explainers.lime import (TabularLIME, TextLIME,
                                              VectorLIME)
    from mmlspark_tpu.explainers.shap import (TabularSHAP, TextSHAP,
                                              VectorSHAP)
    from mmlspark_tpu.featurize.assemble import VectorAssembler
    from mmlspark_tpu.featurize.clean import CleanMissingData
    from mmlspark_tpu.featurize.convert import DataConversion
    from mmlspark_tpu.featurize.featurize import Featurize
    from mmlspark_tpu.featurize.indexer import IndexToValue, ValueIndexer
    from mmlspark_tpu.featurize.select import CountSelector
    from mmlspark_tpu.featurize.text import (MultiNGram, PageSplitter,
                                             TextFeaturizer)
    from mmlspark_tpu.image.transformer import (ImageSetAugmenter,
                                                ImageTransformer, UnrollImage)
    from mmlspark_tpu.image.superpixel import SuperpixelTransformer
    from mmlspark_tpu.isolationforest.iforest import IsolationForest
    from mmlspark_tpu.models.gbdt.estimators import (LightGBMClassifier,
                                                     LightGBMRanker,
                                                     LightGBMRegressor)
    from mmlspark_tpu.models.vw.bandit import VowpalWabbitContextualBandit
    from mmlspark_tpu.models.vw.cse import (VowpalWabbitCSETransformer,
                                            VowpalWabbitDSJsonTransformer)
    from mmlspark_tpu.models.vw.featurizer import (VowpalWabbitFeaturizer,
                                                   VowpalWabbitInteractions)
    from mmlspark_tpu.models.vw.learners import (VowpalWabbitClassifier,
                                                 VowpalWabbitGeneric,
                                                 VowpalWabbitGenericProgressive,
                                                 VowpalWabbitRegressor)
    from mmlspark_tpu.nn.knn import KNN, ConditionalKNN
    from mmlspark_tpu.onnx.model import ONNXModel
    from mmlspark_tpu.recommendation.ranking import (
        RankingAdapter, RankingTrainValidationSplit)
    from mmlspark_tpu.recommendation.sar import SAR
    from mmlspark_tpu.stages.balance import (ClassBalancer,
                                             StratifiedRepartition)
    from mmlspark_tpu.stages.basic import (Cacher, DropColumns, Explode,
                                           Lambda, MultiColumnAdapter,
                                           RenameColumn, Repartition,
                                           SelectColumns, UDFTransformer,
                                           UnicodeNormalize)
    from mmlspark_tpu.stages.batching import (DynamicMiniBatchTransformer,
                                              FixedMiniBatchTransformer,
                                              FlattenBatch,
                                              PartitionConsolidator,
                                              TimeIntervalMiniBatchTransformer)
    from mmlspark_tpu.stages.text import EnsembleByKey
    from mmlspark_tpu.stages.summarize import SummarizeData
    from mmlspark_tpu.stages.text import TextPreprocessor
    from mmlspark_tpu.stages.timer import Timer
    from mmlspark_tpu.train.statistics import (ComputeModelStatistics,
                                               ComputePerInstanceStatistics)
    from mmlspark_tpu.train.trainers import TrainClassifier, TrainRegressor

    tab = _tabular()
    small_gbdt = dict(numIterations=3, numLeaves=4, maxBin=16)
    scored = tab.with_columns({
        "prediction": tab.col("label"),
        "probability": np.stack([1 - tab.col("label"),
                                 tab.col("label")], axis=1)})
    panel = DataFrame.from_rows([
        {"unit": u, "time": t, "outcome": float(u + t + 2.0 * (u < 2 and t > 2)),
         "treatment": float(u < 2), "postTreatment": float(t > 2)}
        for u in range(6) for t in range(6)])
    dsjson = DataFrame({"value": _obj_col([
        '{"EventId":"e1","_label_probability":0.5,"_label_cost":-1.0,'
        '"_labelIndex":0,"p":[0.6,0.4],"a":[1,2]}'] * 6)})
    cb_df = DataFrame({
        "features": _rng.normal(size=(20, 3)),
        "chosenAction": (np.arange(20) % 2 + 1).astype(np.float64),
        "label": _rng.random(20),
        "probability": np.full(20, 0.5),
    })
    access = DataFrame.from_rows([
        {"tenant": 0, "user": f"u{i % 6}", "res": f"r{(i % 6) // 2}",
         "likelihood": 1.0} for i in range(30)])

    onnx_bytes = _tiny_onnx_model()

    reg: Dict[str, TestObject] = {
        # featurize
        "VectorAssembler": TestObject(
            VectorAssembler(inputCols=["x1", "x2"], outputCol="v"), tab),
        "CleanMissingData": TestObject(
            CleanMissingData(inputCols=["x1"], outputCols=["x1c"]), tab),
        "DataConversion": TestObject(
            DataConversion(cols=["x1"], convertTo="double"), tab),
        "Featurize": TestObject(
            Featurize(inputCols=["x1", "cat"], outputCol="f"), tab),
        "ValueIndexer": TestObject(
            ValueIndexer(inputCol="cat", outputCol="cat_idx"), tab),
        "IndexToValue": TestObject(
            IndexToValue(inputCol="cat_idx", outputCol="cat_back"),
            ValueIndexer(inputCol="cat", outputCol="cat_idx").fit(tab)
            .transform(tab)),
        "CountSelector": TestObject(
            CountSelector(inputCol="features", outputCol="sel"), tab),
        "TextFeaturizer": TestObject(
            TextFeaturizer(inputCol="text", outputCol="tf",
                           numFeatures=64), tab),
        "MultiNGram": TestObject(
            MultiNGram(inputCol="text", outputCol="ngrams",
                       lengths=[1, 2]), tab),
        "PageSplitter": TestObject(
            PageSplitter(inputCol="text", outputCol="pages",
                         maximumPageLength=8), tab),
        # stages
        "DropColumns": TestObject(DropColumns(cols=["cat"]), tab),
        "SelectColumns": TestObject(SelectColumns(cols=["x1", "label"]), tab),
        "RenameColumn": TestObject(
            RenameColumn(inputCol="x1", outputCol="x1r"), tab),
        "UDFTransformer": TestObject(
            UDFTransformer(inputCol="x1", outputCol="x1sq",
                           udf=lambda a: np.asarray(a) ** 2), tab,
            skip_serialization=True),  # callables don't round-trip
        "Lambda": TestObject(
            Lambda(transformFunc=lambda df: df.with_column(
                "c", df.col("x1"))), tab, skip_serialization=True),
        "EnsembleByKey": TestObject(
            EnsembleByKey(keys=["cat"], cols=["x1"]), tab),
        "Cacher": TestObject(Cacher(), tab),
        "Repartition": TestObject(Repartition(n=2), tab),
        "Explode": TestObject(
            Explode(inputCol="pages", outputCol="page"),
            PageSplitter(inputCol="text", outputCol="pages",
                         maximumPageLength=8).transform(tab)),
        "UnicodeNormalize": TestObject(
            UnicodeNormalize(inputCol="text", outputCol="norm"), tab),
        "MultiColumnAdapter": TestObject(
            MultiColumnAdapter(inputCols=["text", "cat"],
                               outputCols=["tn", "cn"],
                               baseStage=UnicodeNormalize()), tab),
        "TimeIntervalMiniBatchTransformer": TestObject(
            TimeIntervalMiniBatchTransformer(millisToWait=1,
                                             maxBatchSize=16), tab),
        "ClassBalancer": TestObject(
            ClassBalancer(inputCol="label"), tab),
        "StratifiedRepartition": TestObject(
            StratifiedRepartition(labelCol="label", numShards=2), tab),
        "FixedMiniBatchTransformer": TestObject(
            FixedMiniBatchTransformer(batchSize=16), tab),
        "DynamicMiniBatchTransformer": TestObject(
            DynamicMiniBatchTransformer(maxBatchSize=16), tab),
        "FlattenBatch": TestObject(
            FlattenBatch(),
            FixedMiniBatchTransformer(batchSize=16).transform(
                tab.select("x1", "label"))),
        "PartitionConsolidator": TestObject(PartitionConsolidator(), tab),
        "SummarizeData": TestObject(SummarizeData(), tab.select("x1", "x2")),
        # exploratory (balance measures)
        "FeatureBalanceMeasure": TestObject(
            FeatureBalanceMeasure(sensitiveCols=["cat"], labelCol="label"),
            tab),
        "DistributionBalanceMeasure": TestObject(
            DistributionBalanceMeasure(sensitiveCols=["cat"]), tab),
        "AggregateBalanceMeasure": TestObject(
            AggregateBalanceMeasure(sensitiveCols=["cat"]), tab),
        "TextPreprocessor": TestObject(
            TextPreprocessor(inputCol="text", outputCol="clean",
                             map={"good": "great"}), tab),
        "Timer": TestObject(
            Timer(stage=ValueIndexer(inputCol="cat", outputCol="ci")), tab),
        # gbdt
        "LightGBMClassifier": TestObject(
            LightGBMClassifier(**small_gbdt), tab, approx=1e-5),
        "LightGBMRegressor": TestObject(
            LightGBMRegressor(**small_gbdt), tab, approx=1e-5),
        "LightGBMRanker": TestObject(
            LightGBMRanker(groupCol="group", **small_gbdt),
            tab.with_column("group", np.repeat(np.arange(6), 10)),
            approx=1e-5),
        # vw
        "VowpalWabbitClassifier": TestObject(
            VowpalWabbitClassifier(numPasses=2), tab, approx=1e-5),
        "VowpalWabbitRegressor": TestObject(
            VowpalWabbitRegressor(numPasses=2), tab, approx=1e-5),
        "VowpalWabbitGeneric": TestObject(
            VowpalWabbitGeneric(numPasses=1), tab, approx=1e-5),
        "VowpalWabbitFeaturizer": TestObject(
            VowpalWabbitFeaturizer(inputCols=["x1", "cat"],
                                   outputCol="vwf"), tab),
        "VowpalWabbitInteractions": TestObject(
            VowpalWabbitInteractions(inputCols=["fa", "fb"], outputCol="q",
                                     numBits=10),
            VowpalWabbitFeaturizer(inputCols=["x2"], outputCol="fb",
                                   numBits=10).transform(
                VowpalWabbitFeaturizer(inputCols=["x1"], outputCol="fa",
                                       numBits=10).transform(tab))),
        "VowpalWabbitContextualBandit": TestObject(
            VowpalWabbitContextualBandit(numActions=2, numPasses=1), cb_df,
            approx=1e-5),
        "VowpalWabbitDSJsonTransformer": TestObject(
            VowpalWabbitDSJsonTransformer(), dsjson),
        "VowpalWabbitCSETransformer": TestObject(
            VowpalWabbitCSETransformer(),
            VowpalWabbitDSJsonTransformer().transform(dsjson)
            .with_column("probabilityPredicted", np.full(6, 0.7))),
        # nn / iforest / recommendation
        "KNN": TestObject(
            KNN(k=2), DataFrame({"features": _rng.normal(size=(20, 3)),
                                 "values": np.arange(20)})),
        "ConditionalKNN": TestObject(
            ConditionalKNN(k=2),
            DataFrame({"features": _rng.normal(size=(20, 3)),
                       "values": np.arange(20),
                       "label": _obj_col(["a", "b"] * 10),
                       "conditioner": _obj_col([["a"]] * 20)})),
        "IsolationForest": TestObject(
            IsolationForest(numEstimators=5), tab, approx=1e-5),
        "SAR": TestObject(SAR(supportThreshold=1), _interactions()),
        "RankingAdapter": TestObject(
            RankingAdapter(recommender=SAR(supportThreshold=1), k=3),
            _interactions()),
        "RankingTrainValidationSplit": TestObject(
            RankingTrainValidationSplit(estimator=SAR(supportThreshold=1),
                                        k=3, trainRatio=0.7),
            _interactions(), skip_serialization=True),
        # train / automl
        "TrainClassifier": TestObject(
            TrainClassifier(labelCol="label",
                            model=LightGBMClassifier(**small_gbdt)),
            tab.select("x1", "x2", "label"), approx=1e-5),
        "TrainRegressor": TestObject(
            TrainRegressor(labelCol="label",
                           model=LightGBMRegressor(**small_gbdt)),
            tab.select("x1", "x2", "label"), approx=1e-5),
        "ComputeModelStatistics": TestObject(
            ComputeModelStatistics(labelCol="label"), scored),
        "ComputePerInstanceStatistics": TestObject(
            ComputePerInstanceStatistics(labelCol="label"), scored),
        "TuneHyperparameters": TestObject(
            TuneHyperparameters(models=[LightGBMClassifier(**small_gbdt)],
                                numFolds=2, numRuns=1,
                                evaluationMetric="accuracy"),
            tab.select("features", "label"), skip_serialization=True),
        "FindBestModel": TestObject(
            FindBestModel(models=[LightGBMClassifier(**small_gbdt).fit(tab)],
                          evaluationMetric="accuracy"),
            tab, skip_serialization=True),
        # explainers
        "TabularLIME": TestObject(
            TabularLIME(model=_linear_model(), inputCols=["x1", "x2"],
                        backgroundData=tab, targetClasses=[1],
                        numSamples=40),
            tab.head(2), skip_serialization=True),
        "VectorLIME": TestObject(
            VectorLIME(model=_linear_model(), backgroundData=tab,
                       targetClasses=[1], numSamples=40),
            tab.head(2), skip_serialization=True),
        "TextLIME": TestObject(
            TextLIME(model=_TextProbe(), inputCol="text",
                     targetClasses=[1], numSamples=30),
            tab.head(2), skip_serialization=True),
        "TabularSHAP": TestObject(
            TabularSHAP(model=_linear_model(), inputCols=["x1", "x2"],
                        backgroundData=tab, targetClasses=[1],
                        numSamples=8, backgroundAverages=4),
            tab.head(2), skip_serialization=True),
        "VectorSHAP": TestObject(
            VectorSHAP(model=_linear_model(), backgroundData=tab,
                       targetClasses=[1], numSamples=8,
                       backgroundAverages=4),
            tab.head(2), skip_serialization=True),
        "TextSHAP": TestObject(
            TextSHAP(model=_TextProbe(), inputCol="text", targetClasses=[1],
                     numSamples=8),
            tab.head(2), skip_serialization=True),
        "ICETransformer": TestObject(
            ICETransformer(model=_linear_model(), kind="average",
                           targetClasses=[1],
                           numericFeatures=[{"name": "x1", "numSplits": 3}]),
            tab.head(5), skip_serialization=True),
        # causal
        "ResidualTransformer": TestObject(
            ResidualTransformer(observedCol="label", predictedCol="x1",
                                outputCol="res"), tab),
        "DoubleMLEstimator": TestObject(
            DoubleMLEstimator(
                treatmentModel=LightGBMRegressor(**small_gbdt),
                outcomeModel=LightGBMRegressor(**small_gbdt), maxIter=1),
            DataFrame({"features": _rng.normal(size=(60, 2)),
                       "treatment": (_rng.random(60) > 0.5).astype(float),
                       "outcome": _rng.normal(size=60)}),
            skip_serialization=True),
        "OrthoForestDMLEstimator": TestObject(
            OrthoForestDMLEstimator(
                treatmentModel=LightGBMRegressor(**small_gbdt),
                outcomeModel=LightGBMRegressor(**small_gbdt),
                numTrees=2, maxDepth=2, minSamplesLeaf=2),
            DataFrame({"features": _rng.normal(size=(60, 2)),
                       "heterogeneityVector": _rng.normal(size=(60, 1)),
                       "treatment": (_rng.random(60) > 0.5).astype(float),
                       "outcome": _rng.normal(size=60)}),
            skip_serialization=True),
        "DiffInDiffEstimator": TestObject(DiffInDiffEstimator(), panel),
        "SyntheticControlEstimator": TestObject(
            SyntheticControlEstimator(), panel, approx=1e-3),
        "SyntheticDiffInDiffEstimator": TestObject(
            SyntheticDiffInDiffEstimator(), panel, approx=1e-3),
        # cyber
        "IdIndexer": TestObject(
            IdIndexer(inputCol="user", outputCol="uid",
                      partitionKey="tenant"), access),
        "PartitionedStandardScaler": TestObject(
            PartitionedStandardScaler(inputCol="likelihood",
                                      outputCol="z"), access),
        "PartitionedMinMaxScaler": TestObject(
            PartitionedMinMaxScaler(inputCol="likelihood", outputCol="s"),
            access),
        "ComplementAccessTransformer": TestObject(
            ComplementAccessTransformer(
                tenantCol="tenant", indexedUserCol="user_idx",
                indexedResCol="res_idx"),
            DataFrame({"tenant": np.zeros(20, np.int64),
                       "user_idx": _rng.integers(1, 6, 20),
                       "res_idx": _rng.integers(1, 6, 20)}),
            skip_serialization=True),  # output is random complement draws
        "AccessAnomaly": TestObject(
            AccessAnomaly(maxIter=30, rankParam=4), access, approx=1e-4),
        # dl
        "DeepVisionClassifier": TestObject(
            DeepVisionClassifier(backbone="simple_cnn", batchSize=8,
                                 maxEpochs=1, labelCol="label"),
            _images(), approx=1e-4),
        "DeepTextClassifier": TestObject(
            DeepTextClassifier(batchSize=8, maxEpochs=1, labelCol="label",
                               maxLength=6, embeddingDim=16, numLayers=1,
                               numHeads=2),
            tab.head(16), approx=1e-4),
        "SentenceEmbedder": TestObject(
            SentenceEmbedder(inputCol="text", outputCol="emb", maxLength=6,
                             allowRandomEncoder=True,
                             embeddingDim=16, numLayers=1, numHeads=2),
            tab.head(8), skip_serialization=True),
        "CausalLM": TestObject(
            CausalLM(inputCol="text", outputCol="completion",
                     modelConfig=dict(
                         hidden_size=32, num_attention_heads=2,
                         num_key_value_heads=1, head_dim=16,
                         intermediate_size=64, vocab_size=128,
                         num_hidden_layers=1, rms_norm_eps=1e-6,
                         rope_theta=1e4),
                     maxNewTokens=3, batchSize=4, maxLength=16,
                     prefillChunk=8, allowRandomWeights=True),
            tab.head(8), approx=1e-5),
        # image
        "ImageTransformer": TestObject(
            ImageTransformer(inputCol="image", outputCol="out").resize(8, 8),
            _images()),
        "ImageSetAugmenter": TestObject(
            ImageSetAugmenter(inputCol="image", outputCol="aug"), _images()),
        "UnrollImage": TestObject(
            UnrollImage(inputCol="image", outputCol="vec"), _images()),
        "SuperpixelTransformer": TestObject(
            SuperpixelTransformer(inputCol="image", cellSize=6.0), _images()),
        # onnx
        "ONNXModel": TestObject(
            ONNXModel(modelPayload=onnx_bytes,
                      feedDict={"x": "features"},
                      fetchDict={"out": "y"}), tab),
    }
    return reg


class _TextProbe(Transformer):
    def _transform(self, df):
        texts = [str(v) for v in df.col("text")]
        score = np.asarray([t.split().count("good") for t in texts],
                           np.float64)
        p = 1 / (1 + np.exp(-(score - 0.5)))
        return df.with_column("probability", np.stack([1 - p, p], axis=1))


def _tiny_onnx_model() -> bytes:
    from mmlspark_tpu.onnx.convert import pb

    w = _rng.normal(size=(2, 1)).astype(np.float32)
    t = pb.TensorProto()
    t.name = "w"
    t.dims.extend(w.shape)
    t.data_type = 1
    t.raw_data = np.ascontiguousarray(w).tobytes()
    n = pb.NodeProto()
    n.op_type = "MatMul"
    n.input.extend(["x", "w"])
    n.output.append("y")
    m = pb.ModelProto()
    m.ir_version = 8
    m.opset_import.add().version = 17
    m.graph.name = "g"
    m.graph.node.append(n)
    vi = pb.ValueInfoProto()
    vi.name = "x"
    vi.type.tensor_type.elem_type = 1
    m.graph.input.append(vi)
    vo = pb.ValueInfoProto()
    vo.name = "y"
    m.graph.output.append(vo)
    m.graph.initializer.append(t)
    return m.SerializeToString()


def fault_point_registry() -> Dict[str, str]:
    """Named fault-injection points the fuzzing/chaos suites can arm
    (the robustness analog of TestObject registration): the canonical
    list lives in :mod:`mmlspark_tpu.core.faults` (``KNOWN_POINTS``);
    this re-export keeps fuzzing drivers decoupled from core imports.
    Arm via ``mmlspark_tpu.core.faults.injected(name, action, ...)`` or
    ``MMLSPARK_TPU_FAULTS="name:action[:nth[:param]]"``. The
    completeness test (tests/gbdt/test_fault_injection.py) pins that
    every production ``fault_point("...")`` call site names a
    registered point."""
    from mmlspark_tpu.core.faults import KNOWN_POINTS
    return dict(KNOWN_POINTS)


# Stages with no TestObject, with the reason (FuzzingTest exemption-list
# parity, FuzzingTest.scala:19-80)
EXEMPT: Dict[str, str] = {
    "Pipeline": "exercised via every composite TestObject",
    "HTTPTransformer": "needs a live endpoint; covered by tests/io",
    "SimpleHTTPTransformer": "needs a live endpoint; covered by tests/io",
    "CognitiveServiceTransformer": "abstract base",
    "OpenAIChatCompletion": "needs a live endpoint; covered by tests/io",
    "OpenAIPrompt": "needs a live endpoint; covered by tests/io",
    "OpenAIEmbedding": "needs a live endpoint; covered by tests/io",
    "TextSentiment": "needs a live endpoint; covered by tests/io",
    "KeyPhraseExtractor": "needs a live endpoint; covered by tests/io",
    "LanguageDetector": "needs a live endpoint; covered by tests/io",
    "EntityRecognizer": "needs a live endpoint; covered by tests/io",
    "PIIRecognizer": "needs a live endpoint; covered by tests/io",
    "Translate": "needs a live endpoint; covered by tests/io",
    "DetectLastAnomaly": "needs a live endpoint; covered by tests/io",
    "DetectAnomalies": "needs a live endpoint; covered by tests/io",
    "AnalyzeImage": "needs a live endpoint; covered by tests/io",
    "DescribeImage": "needs a live endpoint; covered by tests/io",
    "OCR": "needs a live endpoint; covered by tests/io",
    "DetectFace": "needs a live endpoint; covered by tests/io",
    "AnalyzeDocument": "needs a live endpoint; covered by tests/io",
    "AnalyzeText": "needs a live endpoint; covered by tests/io",
    "AddDocuments": "needs a live endpoint; covered by tests/io",
    "SpeechToText": "needs a live endpoint; covered by tests/io",
    "SpeechToTextSDK": "needs a live endpoint; covered by tests/io",
    "TextToSpeech": "needs a live endpoint; covered by tests/io",
    "BingImageSearch": "needs a live endpoint; covered by tests/io",
    "AddressGeocoder": "needs a live endpoint; covered by tests/io",
    "ReverseAddressGeocoder": "needs a live endpoint; covered by tests/io",
    "CheckPointInPolygon": "needs a live endpoint; covered by tests/io",
    "FitMultivariateAnomaly": "needs a live endpoint; covered by tests/io",
    "ImageFeaturizer": "covered by tests/onnx with a real graph",
    "ImageLIME": "superpixel loop too slow for fuzzing; tests/explainers",
    "ImageSHAP": "superpixel loop too slow for fuzzing; tests/explainers",
    "LocalExplainer": "abstract base",
    "DeepEstimator": "abstract base",
    "VowpalWabbitGenericProgressive":
        "transform-only progressive mode; covered by tests/vw",
}
