"""ONNXModel transformer + ImageFeaturizer + hub stub.

Parity: onnx/ONNXModel.scala:211-256 — feedDict (model input name ->
DataFrame column), fetchDict (output column -> graph tensor name, which
may be an INTERMEDIATE tensor: the graph is sliced there exactly like
sliceAtOutputs, :207), miniBatchSize batching, softMaxDict/argMaxDict
post-ops (:255-301). ImageFeaturizer (onnx/ImageFeaturizer.scala:34)
chains ImageTransformer preprocessing into a headless network.

TPU-first: one jitted graph evaluation per batch; the reference's
per-task GPU selection (ONNXRuntime.scala:47-57) is unnecessary — XLA
owns the chip, and batch rows shard over cores via the mesh.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.param import (
    HasInputCol, HasOutputCol, Param, gt, to_int, to_str,
)
from mmlspark_tpu.core.pipeline import Transformer
from mmlspark_tpu.core.timer import span
from mmlspark_tpu.onnx.convert import OnnxGraph, load_model
from mmlspark_tpu.ops.ingest import RowSource


class ONNXModel(Transformer):
    modelPayload = Param("modelPayload", "ONNX model bytes", is_complex=True)
    feedDict = Param("feedDict", "model input name -> input column",
                     is_complex=True)
    fetchDict = Param("fetchDict", "output column -> graph tensor name",
                      is_complex=True)
    miniBatchSize = Param("miniBatchSize", "rows per device batch", to_int,
                          gt(0), default=256)
    softMaxDict = Param("softMaxDict", "input col -> output col softmax "
                        "post-op", is_complex=True)
    argMaxDict = Param("argMaxDict", "input col -> output col argmax "
                       "post-op", is_complex=True)

    _graph: Optional[OnnxGraph] = None
    _scorer = None
    _mesh = None

    def set_model_location(self, path: str) -> "ONNXModel":
        with open(path, "rb") as f:
            self._set(modelPayload=f.read())
        return self

    def set_mesh(self, mesh) -> "ONNXModel":
        """Shard each minibatch's rows over the mesh 'dp' axis — the
        embarrassing-parallel scoring mode (model broadcast + partition
        scoring, onnx/ONNXModel.scala:242-251)."""
        self._mesh = mesh
        self._scorer = None
        return self

    def _ensure_graph(self):
        if self._graph is None:
            fetch = self.get("fetchDict") or {}
            outputs = list(fetch.values()) or None
            self._graph = OnnxGraph(load_model(self.get("modelPayload")),
                                    outputs)
            self._scorer = None
        return self._graph

    def _ensure_scorer(self):
        """The shared scoring engine: float initializers lifted into a
        params pytree resident on-device under the onnx rule table,
        batches bucket-padded and row-sharded over dp."""
        self._ensure_graph()
        if self._scorer is None:
            from mmlspark_tpu.parallel.shard_rules import ShardedScorer
            run, weights = self._graph.convert_trainable()
            self._scorer = ShardedScorer(
                run, weights, family="onnx", mesh=self._mesh,
                max_batch=self.get("miniBatchSize"), label="onnx")
        return self._scorer

    def shard_metadata(self) -> Dict[str, Any]:
        """Resolved sharding mode + reason (the warn-once downgrade
        contract's queryable side)."""
        return self._ensure_scorer().metadata()

    @property
    def model_inputs(self) -> Dict[str, tuple]:
        return dict(self._ensure_graph().input_shapes)

    @property
    def model_outputs(self) -> List[str]:
        return list(self._ensure_graph().all_output_names)

    def _transform(self, dataset: DataFrame) -> DataFrame:
        graph = self._ensure_graph()
        scorer = self._ensure_scorer()
        feed = self.get("feedDict") or {
            graph.input_names[0]: "features"}
        fetch = self.get("fetchDict") or {
            "output": graph.output_names[0]}

        # spans (core/timer.py): ``onnx.stack`` is an object column laid
        # out as one array, here only checked: the engine lays it out, a
        # chunk at a time where a group is over a chunk's bytes, each
        # lay-out an ``onnx.stack`` again. ``onnx.cast`` is the dtype
        # pass (of an object column: the dtype its lay-outs cast to),
        # ``onnx.columns`` the output columns and post-ops; the engine's
        # own are ``scorer.*``
        feeds = {}
        for input_name, col_name in feed.items():
            col = dataset.col(col_name)
            with span("onnx.stack", rows=len(col)) as stack:
                batch = (RowSource(col, "onnx.stack") if col.dtype == object
                         else col)
                stack.counts["bytes"] = batch.nbytes
            # honor the graph's declared input dtype; otherwise keep
            # int/bool columns intact and only downcast f64 -> f32
            with span("onnx.cast"):
                declared = graph.input_dtypes.get(input_name)
                if declared is None and batch.dtype == np.float64:
                    declared = np.float32
                if declared is not None:
                    batch = (batch.astype(declared)
                             if isinstance(batch, RowSource)
                             else np.asarray(batch, declared))
                feeds[input_name] = batch
        # one engine call: the scorer chunks to miniBatchSize-capped
        # bucket rungs internally and keeps weights resident on-device
        fetched = scorer(feeds)

        with span("onnx.columns"):
            out = dataset
            for out_col, tensor_name in fetch.items():
                stacked = np.asarray(fetched[tensor_name])
                if stacked.ndim > 2:  # ragged-safe object column
                    obj = np.empty(len(stacked), dtype=object)
                    for i in range(len(stacked)):
                        obj[i] = stacked[i]
                    stacked = obj
                out = out.with_column(out_col, stacked)

            import jax
            for src, dst in (self.get("softMaxDict") or {}).items():
                vals = np.asarray(list(out.col(src)), np.float64)
                out = out.with_column(dst, np.asarray(
                    jax.nn.softmax(vals, axis=-1)))
            for src, dst in (self.get("argMaxDict") or {}).items():
                vals = np.asarray(list(out.col(src)), np.float64)
                out = out.with_column(dst, vals.argmax(axis=-1)
                                      .astype(np.float64))
        return out

    def slice_at_output(self, tensor_name: str,
                        output_col: str = "output") -> "ONNXModel":
        """New ONNXModel fetching an intermediate tensor
        (ONNXModel.sliceAtOutputs parity)."""
        clone = self.copy(fetchDict={output_col: tensor_name})
        clone._graph = None
        clone._scorer = None
        return clone


class ImageFeaturizer(Transformer, HasInputCol, HasOutputCol):
    """image column -> preprocessing -> headless ONNX net -> feature
    vector (onnx/ImageFeaturizer.scala:34)."""

    onnxModel = Param("onnxModel", "the ONNXModel to run", is_complex=True)
    headless = Param("headless", "fetch the penultimate (feature) tensor "
                     "instead of the classifier output", is_complex=False,
                     converter=lambda v: bool(v), default=True)
    featureTensorName = Param("featureTensorName", "tensor to fetch in "
                              "headless mode (default: input of the last "
                              "node)", to_str)
    imageHeight = Param("imageHeight", "resize height", to_int, gt(0))
    imageWidth = Param("imageWidth", "resize width", to_int, gt(0))
    channelOrderNCHW = Param("channelOrderNCHW", "emit NCHW float tensors",
                             is_complex=False, converter=lambda v: bool(v),
                             default=True)

    def _transform(self, dataset: DataFrame) -> DataFrame:
        from mmlspark_tpu.image import ImageTransformer

        onnx_model: ONNXModel = self.get("onnxModel")
        graph = onnx_model._ensure_graph()

        df = dataset
        it = ImageTransformer(inputCol=self.get("inputCol"),
                              outputCol="__img__",
                              toTensor=self.get("channelOrderNCHW"))
        if self.is_set("imageHeight") != self.is_set("imageWidth"):
            raise ValueError("imageHeight and imageWidth must be set "
                             "together")
        if self.is_set("imageHeight"):
            it = it.resize(self.get("imageHeight"), self.get("imageWidth"))
        df = it.transform(df)

        if self.get("headless"):
            tensor = self.get("featureTensorName")
            if not tensor:
                last = graph.model.graph.node[-1]
                tensor = last.input[0]
            scorer = onnx_model.copy(
                feedDict={graph.input_names[0]: "__img__"},
                fetchDict={self.get("outputCol"): tensor})
        else:
            scorer = onnx_model.copy(
                feedDict={graph.input_names[0]: "__img__"},
                fetchDict={self.get("outputCol"): graph.all_output_names[0]})
        scorer._graph = None
        scorer._scorer = None
        out = scorer.transform(df)
        feats = out.col(self.get("outputCol"))
        if feats.dtype == object:  # flatten feature maps to vectors
            flat = np.stack([np.asarray(v).reshape(-1) for v in feats])
            out = out.with_column(self.get("outputCol"), flat)
        return out.drop("__img__")


class ONNXHub:
    """Local model zoo with a JSON manifest + checksum verification.

    The reference hub (onnx/ONNXHub.scala:72-99) fetches a manifest of
    models and caches verified downloads. Zero-egress redesign: the hub
    root is a local directory holding ``manifest.json`` — entries of
    ``{"model": name, "model_path": relpath, "model_sha256": hex,
    "tags": [...]}`` — and the model files; ``get_model`` verifies the
    checksum and memoizes bytes, ``register_model`` builds the manifest.
    """

    MANIFEST = "manifest.json"

    def __init__(self, hub_dir: str):
        import os
        self.hub_dir = hub_dir
        os.makedirs(hub_dir, exist_ok=True)
        self._cache: Dict[str, bytes] = {}

    def _manifest_path(self) -> str:
        import os
        return os.path.join(self.hub_dir, self.MANIFEST)

    def _read_manifest(self) -> List[Dict[str, Any]]:
        import json
        import os
        if not os.path.exists(self._manifest_path()):
            return []
        with open(self._manifest_path()) as f:
            return json.load(f)

    def list_models(self, tags: Optional[List[str]] = None
                    ) -> List[Dict[str, Any]]:
        """Manifest entries, optionally filtered to those carrying ALL
        the given tags (ONNXHub.listModels parity)."""
        entries = self._read_manifest()
        if tags:
            want = set(tags)
            entries = [e for e in entries
                       if want.issubset(set(e.get("tags", [])))]
        return entries

    def get_model_info(self, name: str) -> Dict[str, Any]:
        for e in self._read_manifest():
            if e["model"] == name:
                return e
        known = [e["model"] for e in self._read_manifest()]
        raise KeyError(f"model {name!r} not in hub manifest; have {known}")

    def get_model(self, name: str) -> bytes:
        """Model bytes, checksum-verified and cached in memory."""
        import hashlib
        import os
        if name in self._cache:
            return self._cache[name]
        info = self.get_model_info(name)
        path = os.path.join(self.hub_dir, info["model_path"])
        with open(path, "rb") as f:
            data = f.read()
        digest = hashlib.sha256(data).hexdigest()
        if info.get("model_sha256") and digest != info["model_sha256"]:
            raise ValueError(
                f"checksum mismatch for {name!r}: manifest "
                f"{info['model_sha256'][:12]}..., file {digest[:12]}...")
        self._cache[name] = data
        return data

    def register_model(self, name: str, payload: bytes,
                       tags: Optional[List[str]] = None) -> Dict[str, Any]:
        """Add a model file + manifest entry (builds local zoos)."""
        import hashlib
        import json
        import os
        import re
        if not re.fullmatch(r"[A-Za-z0-9._-]+", name) or ".." in name:
            raise ValueError(
                f"model name {name!r} must be a plain identifier "
                f"(letters, digits, . _ -); path separators would escape "
                f"the hub directory")
        rel = f"{name}.onnx"
        with open(os.path.join(self.hub_dir, rel), "wb") as f:
            f.write(payload)
        entry = {"model": name, "model_path": rel,
                 "model_sha256": hashlib.sha256(payload).hexdigest(),
                 "tags": list(tags or [])}
        entries = [e for e in self._read_manifest() if e["model"] != name]
        entries.append(entry)
        with open(self._manifest_path(), "w") as f:
            json.dump(entries, f, indent=1)
        self._cache.pop(name, None)
        return entry

    def load_model(self, name: str) -> "ONNXModel":
        """ONNXModel ready to transform (getModel -> scorer parity)."""
        return ONNXModel(modelPayload=self.get_model(name))
