"""Unified sharding-rules layer: regex -> PartitionSpec per model family.

The reference ships a bespoke distribution story per estimator
(LightGBM's native ring, VW's spanning tree, ONNX/DNN broadcast);
our mesh plumbing had grown the same way — GBDT threads its own
specs, VW pmaps, dl/onnx re-``device_put`` per batch. This module
makes placement a declarative, system-level decision instead
(arXiv:2004.13336 makes the case for data-parallel weight updates;
arXiv:1605.08695 for a single placement layer under many workloads):

- ``*_RULES`` — an ordered ``(regex, spec)`` table per model family.
  A spec is a tuple of mesh-axis names (or ``None``) applied
  left-aligned to the leaf's dims, ``()`` meaning fully replicated.
  First match whose rank fits wins; anything unmatched replicates
  with a ``warn_once`` naming the leaf (no silent fallback).
- ``make_shard_and_gather_fns`` — per-leaf shard/gather callables
  with optional dtype casting (``MMLSPARK_TPU_INFER_AUTOCAST=bf16``
  casts resident float weights; off by default, parity-pinned).
- ``ShardedScorer`` — the shared pjit scoring engine every
  ``transform`` routes through: model pytrees stay resident
  on-device under their rule-derived shardings, batches pad to a
  pow2 bucket ladder (one compile per rung, counted under
  graftsan), and rows shard over ``dp``.

Bitwise contract: the engine's unit of compilation is a fixed
per-device micro-batch rung chosen from the ladder by row count
(and, for ragged sequences, a second rung by length) — never by mesh
size — and each dispatch feeds ``dp x rung`` rows sharded over ``dp``. XLA:CPU (and TPU) matmul numerics vary
with the batch dimension, so keeping the per-device shape constant
across dp is what makes dp=1/2/8 outputs bitwise-identical to each
other and to the serial chunked path (pinned by
tests/parallel/test_shard_rules.py).
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from mmlspark_tpu.core.logging_utils import warn_once
from mmlspark_tpu.core.timer import span
from mmlspark_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    axis_size,
)

# Leaves at or below this element count replicate regardless of rules:
# sharding a bias vector buys nothing and costs a reshard. Matches the
# "scalar/small leaves replicated" convention of the exemplar tables.
SMALL_LEAF_NUMEL = 65536

# Training-state tables use a lower threshold: an optimizer moment is
# touched once per step (not once per row), so sharding pays off at
# much smaller sizes — and the ZeRO-1 memory win must materialize on
# test-scale models too.
TRAIN_SMALL_LEAF_NUMEL = 4096

# Per-family rule tables. Specs are tuples over the leaf's dims,
# left-aligned like PartitionSpec; axis names must be mesh.py *_AXIS
# constants (GL001 checks these statically). Scoring is row-parallel —
# the batch shards over dp at dispatch — so parameter leaves default
# to replication; the mp entries shard the large dense kernels of
# deep/onnx models across the model axis when the mesh has one
# (mp=1 meshes make them no-ops, keeping numerics bitwise).
GBDT_RULES: List[Tuple[str, Tuple]] = [
    # tree arrays (split_feature, thresholds, node values) are small
    # and traversed by every row: replicate everything
    (r".*", ()),
]

VW_RULES: List[Tuple[str, Tuple]] = [
    # the linear weight vector is read by every row's dot product
    (r".*", ()),
]

ONNX_RULES: List[Tuple[str, Tuple]] = [
    # large 2-d initializers (dense kernels) shard over mp; everything
    # else — biases, norms, scalars — replicates
    (r".*", (None, MODEL_AXIS)),
    (r".*", ()),
]

DL_RULES: List[Tuple[str, Tuple]] = [
    (r".*embedding.*", (MODEL_AXIS, None)),
    (r".*kernel$", (None, MODEL_AXIS)),
    # stacked expert weights (experts, in, out): the expert axis over
    # mp. No scorer runs such a mesh yet (the experts' exchange across
    # chips is not written); without an mp axis the leaf replicates,
    # placed once at construction like every other
    (r".*experts_(gate|up|down)$", (MODEL_AXIS, None, None)),
    (r".*", ()),
]

FAMILY_RULES: Dict[str, List[Tuple[str, Tuple]]] = {
    "gbdt": GBDT_RULES,
    "vw": VW_RULES,
    "onnx": ONNX_RULES,
    "dl": DL_RULES,
}

# Training-state placement (ZeRO-1, arXiv:2004.13336): optimizer
# moments and large param leaves partition over dp on the first dim
# the axis divides; small leaves (<= TRAIN_SMALL_LEAF_NUMEL) replicate
# via the match_partition_rules threshold. Each replica owns one shard
# of the weight update — grads reduce-scatter into it, updated params
# all-gather out of it (dl/estimator.py wires the constraints).
DL_TRAIN_RULES: List[Tuple[str, Tuple]] = [
    (r".*", (DATA_AXIS, None)),
    (r".*", (None, DATA_AXIS)),
    (r".*", (DATA_AXIS, None, None)),
    (r".*", (DATA_AXIS,)),
    (r".*", ()),
]

TRAIN_FAMILY_RULES: Dict[str, List[Tuple[str, Tuple]]] = {
    "dl": DL_TRAIN_RULES,
}


def _leaf_paths(tree) -> List[Tuple[str, Any]]:
    """(name, leaf) pairs with '/'-joined key paths."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        parts = []
        for p in path:
            key = getattr(p, "key", getattr(p, "idx", getattr(p, "name",
                                                              None)))
            parts.append(str(key))
        out.append(("/".join(parts) if parts else "", leaf))
    return out


def _spec_fits(spec: Tuple, leaf, mesh) -> bool:
    """A rule applies only when its rank matches the leaf and every
    named axis exists in the mesh and divides the dim it shards."""
    ndim = getattr(leaf, "ndim", 0)
    shape = getattr(leaf, "shape", ())
    if spec == ():
        return True
    if len(spec) != ndim:
        return False
    for dim, entry in zip(shape, spec):
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in axes:
            if ax is None:
                continue
            if mesh is None or ax not in mesh.axis_names:
                return False
            if dim % axis_size(mesh, ax):
                return False
    return True


def match_partition_rules(rules: List[Tuple[str, Tuple]], params,
                          mesh=None, label: str = "model",
                          small_numel: int = SMALL_LEAF_NUMEL):
    """Map a param pytree to a pytree of spec tuples via the rule table.

    Scalars and leaves at or below ``small_numel`` elements replicate
    before rules apply (training-state tables pass the lower
    TRAIN_SMALL_LEAF_NUMEL threshold). The first rule whose regex
    matches the '/'-joined leaf name AND whose spec fits the leaf's
    rank/shape on this mesh wins. A leaf no rule matches falls back to
    replication with a ``warn_once`` naming the leaf — the downgrade
    contract: no silent placement decisions.
    """
    import jax

    specs = _match_rules_flat(rules, params, mesh, label, small_numel)
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(treedef, specs)


def _match_rules_flat(rules: List[Tuple[str, Tuple]], params, mesh,
                      label: str, small_numel: int) -> List[Tuple]:
    """Flat spec list in ``tree_leaves(params)`` order (the tree-free
    core of :func:`match_partition_rules`; training-state helpers use
    it directly because optax states are namedtuples, which a
    tuple-leaved spec pytree cannot round-trip through)."""
    named = _leaf_paths(params)
    specs = []
    for name, leaf in named:
        ndim = getattr(leaf, "ndim", 0)
        numel = int(np.prod(getattr(leaf, "shape", ()) or (1,)))
        if ndim == 0 or numel <= small_numel:
            specs.append(())
            continue
        for pattern, spec in rules:
            if re.search(pattern, name) and _spec_fits(spec, leaf, mesh):
                specs.append(spec)
                break
        else:
            warn_once(f"shard_rules.unmatched.{label}.{name}",
                      "shard_rules: no rule in the %s table fits leaf "
                      "%r (shape %s) on this mesh; replicating",
                      label, name, tuple(getattr(leaf, "shape", ())))
            specs.append(())
    return specs


def spec_to_pspec(spec: Tuple):
    import jax

    return jax.sharding.PartitionSpec(*spec)


def resolve_infer_autocast() -> str:
    """MMLSPARK_TPU_INFER_AUTOCAST: off (default, parity-pinned) or
    bf16. Unknown values warn once and fall back to off."""
    from mmlspark_tpu.core.env import env_str

    mode = (env_str("MMLSPARK_TPU_INFER_AUTOCAST", "off") or "off")
    mode = mode.strip().lower() or "off"
    if mode not in ("off", "bf16"):
        warn_once("shard_rules.autocast.unknown",
                  "MMLSPARK_TPU_INFER_AUTOCAST=%r not in off|bf16; "
                  "using off", mode)
        mode = "off"
    return mode


def placement_cast(x, dtype):
    """THE sanctioned low-precision placement seam: cast float ``x``
    to ``dtype`` (None or a non-float ``x`` passes through unchanged).

    Every low-precision cast in the tree must route through here —
    graftlint GL015 flags any other ``astype(bfloat16)`` in the repo —
    so bf16 placement stays behind :func:`resolve_infer_autocast`'s
    warn-once policy and the graftsan dtype contract sees one seam."""
    import jax.numpy as jnp

    v = jnp.asarray(x)
    if dtype is not None and jnp.issubdtype(v.dtype, jnp.floating):
        return v.astype(dtype)
    return v


def make_shard_and_gather_fns(partition_specs, mesh=None,
                              dtype_specs=None):
    """Per-leaf (shard_fns, gather_fns) pytrees.

    ``shard_fns`` place a host leaf on-device under its rule-derived
    NamedSharding (or as a plain committed array when ``mesh`` is
    None), optionally casting float leaves to ``dtype_specs`` via
    :func:`placement_cast` (a single dtype — the bf16 autocast path;
    None leaves dtypes alone). ``gather_fns`` fetch back to host
    numpy.
    """
    import jax

    def make_shard(spec):
        def shard(x):
            v = placement_cast(x, dtype_specs)
            if mesh is not None:
                sharding = jax.sharding.NamedSharding(
                    mesh, spec_to_pspec(spec))
                return jax.device_put(v, sharding)
            return v
        return shard

    def make_gather(spec):
        def gather(x):
            return np.asarray(jax.device_get(x))
        return gather

    is_spec = lambda s: isinstance(s, tuple)  # noqa: E731
    shard_fns = jax.tree_util.tree_map(make_shard, partition_specs,
                                       is_leaf=is_spec)
    gather_fns = jax.tree_util.tree_map(make_gather, partition_specs,
                                        is_leaf=is_spec)
    return shard_fns, gather_fns


def resolve_shard_rules(mesh, label: str = "model") -> Tuple[str, str]:
    """Resolve the engine mode from MMLSPARK_TPU_SHARD_RULES + mesh.

    Returns ``(mode, reason)``: mode is ``rules`` (rule-table
    shardings over the mesh), ``replicate`` (mesh present but without
    a dp axis — params replicated, batch unsharded), or ``serial``
    (single-device). Downgrades warn once; the pair is recorded in
    model metadata and surfaced by bench/serving so every measurement
    names its placement.
    """
    from mmlspark_tpu.core.env import env_str

    knob = (env_str("MMLSPARK_TPU_SHARD_RULES", "auto") or "auto")
    knob = knob.strip().lower() or "auto"
    if knob not in ("auto", "on", "off"):
        warn_once("shard_rules.knob.unknown",
                  "MMLSPARK_TPU_SHARD_RULES=%r not in auto|on|off; "
                  "using auto", knob)
        knob = "auto"
    if knob == "off":
        return "serial", "disabled by MMLSPARK_TPU_SHARD_RULES=off"
    if mesh is None:
        if knob == "on":
            warn_once(f"shard_rules.no_mesh.{label}",
                      "MMLSPARK_TPU_SHARD_RULES=on but %s carries no "
                      "mesh; serial single-device fallback", label)
            return "serial", "requested on, but no mesh attached"
        return "serial", "no mesh attached"
    if DATA_AXIS not in mesh.axis_names:
        warn_once(f"shard_rules.no_dp.{label}",
                  "shard_rules: mesh for %s has no %r axis; params "
                  "replicate and the batch stays unsharded",
                  label, DATA_AXIS)
        return "replicate", f"mesh lacks the {DATA_AXIS!r} axis"
    return "rules", f"rule table over {mesh.devices.size}-device mesh"


def resolve_train_shard(mesh, label: str = "fit") -> Tuple[str, str]:
    """Resolve the training-state mode from MMLSPARK_TPU_TRAIN_SHARD +
    the fit mesh.

    Returns ``(mode, reason)``: ``sharded`` (ZeRO-1 — optimizer
    moments partitioned over dp via DL_TRAIN_RULES, grads
    reduce-scattered, params all-gathered after the owned-shard
    update) or ``replicated`` (the legacy fully replicated update).
    Downgrades warn once and the pair lands in the fitted model's
    ``shard_metadata()`` — same contract as :func:`resolve_shard_rules`.
    """
    from mmlspark_tpu.core.env import env_str

    knob = (env_str("MMLSPARK_TPU_TRAIN_SHARD", "auto") or "auto")
    knob = knob.strip().lower() or "auto"
    if knob not in ("auto", "on", "off"):
        warn_once("train_shard.knob.unknown",
                  "MMLSPARK_TPU_TRAIN_SHARD=%r not in auto|on|off; "
                  "using auto", knob)
        knob = "auto"
    if knob == "off":
        return "replicated", "disabled by MMLSPARK_TPU_TRAIN_SHARD=off"
    if mesh is None:
        if knob == "on":
            warn_once(f"train_shard.no_mesh.{label}",
                      "MMLSPARK_TPU_TRAIN_SHARD=on but %s carries no "
                      "mesh; training state stays replicated", label)
            return "replicated", "requested on, but no mesh attached"
        return "replicated", "no mesh attached"
    if DATA_AXIS not in mesh.axis_names:
        if knob == "on":
            warn_once(f"train_shard.no_dp.{label}",
                      "MMLSPARK_TPU_TRAIN_SHARD=on but the mesh for %s "
                      "has no %r axis; training state stays replicated",
                      label, DATA_AXIS)
        return "replicated", f"mesh lacks the {DATA_AXIS!r} axis"
    return "sharded", (f"ZeRO-1 over dp={axis_size(mesh, DATA_AXIS)} "
                       f"({mesh.devices.size}-device mesh)")


def train_state_shardings(state, mesh, label: str = "train_state",
                          family: str = "dl"):
    """NamedSharding pytree for a training-state pytree (params, grads,
    or optimizer state) under the family's *_TRAIN_RULES table with the
    training-state small-leaf threshold. Built leaf-wise (optax states
    are namedtuples, so spec tuples cannot live as pytree leaves)."""
    import jax

    specs = _match_rules_flat(TRAIN_FAMILY_RULES[family], state, mesh,
                              label, TRAIN_SMALL_LEAF_NUMEL)
    shardings = [jax.sharding.NamedSharding(mesh, spec_to_pspec(s))
                 for s in specs]
    treedef = jax.tree_util.tree_structure(state)
    return jax.tree_util.tree_unflatten(treedef, shardings)


def train_state_bytes_per_device(state, mesh, label: str = "train_state",
                                 family: str = "dl") -> int:
    """Analytic per-device bytes of ``state`` under its *_TRAIN_RULES
    placement: sharded leaves contribute nbytes / (product of their
    axis sizes), replicated leaves full nbytes — the optimizer-state
    memory model the train-shard metadata and MULTICHIP row report.
    ``mesh=None`` gives the fully replicated total."""
    named = _leaf_paths(state)
    specs = (_match_rules_flat(TRAIN_FAMILY_RULES[family], state, mesh,
                               label, TRAIN_SMALL_LEAF_NUMEL)
             if mesh is not None else [() for _ in named])
    total = 0
    for (_, leaf), spec in zip(named, specs):
        nbytes = int(np.prod(getattr(leaf, "shape", ()) or (1,))
                     * np.dtype(getattr(leaf, "dtype",
                                        np.float32)).itemsize)
        denom = 1
        for entry in spec:
            axes = entry if isinstance(entry, tuple) else (entry,)
            for ax in axes:
                if ax is not None and mesh is not None \
                        and ax in mesh.axis_names:
                    denom *= axis_size(mesh, ax)
        total += nbytes // denom
    return total


class ShardedScorer:
    """Shared pjit scoring engine for transform/inference.

    ``apply_fn(params, batch)`` plus a params pytree (or a pre-jitted
    closure ``fn(batch)`` with ``params=None`` — the GBDT boosters
    keep their arrays as jit constants). The batch is an ndarray or a
    dict of ndarrays sharing the leading row dim.

    On construction the params shard once onto the mesh under their
    family rule table and stay resident — no per-batch ``device_put``
    of model state; ``param_dtype`` is the dtype the model itself
    states (float leaves are placed in it through ``placement_cast``;
    unset, the ``MMLSPARK_TPU_INFER_AUTOCAST`` policy decides). Each
    call picks a per-device rung from the pow2 ladder by row count,
    pads with zero rows, and dispatches ``dp x rung`` rows sharded over
    ``dp``; compile count is bounded by the ladder and counted under
    graftsan. Input buffers are donated on non-CPU backends (XLA:CPU
    device_put aliases host numpy, so donation there could hand the
    user's buffer to XLA); of a chunked feed, what is donated is the
    concatenated array, and the chunks are dropped once it exists.

    Ragged sequences take a second ladder, over length
    (``max_length``): ``length_batches(lengths)`` sorts the rows by
    length and cuts them into device batches, each padded to the length
    rung of its longest row; the caller pads each batch to its rung and
    scores it as a dict batch (``{"ids", "lengths"}``), so one program
    compiles a (row rung, length rung). With ``jit=False`` the engine
    places the params and calls ``apply_fn(params, batch)`` as it is: a
    stage whose call is several programs (a prefill, then a decode that
    takes the state donated) brings its own, named, jits.
    """

    def __init__(self, apply_fn: Callable, params=None,
                 family: str = "gbdt", mesh=None, *,
                 max_batch: int = 1024, label: str = "scorer",
                 param_dtype=None, max_length: Optional[int] = None,
                 jit: bool = True):
        import jax

        from mmlspark_tpu.parallel.inference import (
            bucket_ladder,
            length_ladder,
        )

        if family not in FAMILY_RULES:
            raise ValueError(f"unknown model family {family!r}; "
                             f"known: {sorted(FAMILY_RULES)}")
        self.family = family
        self.label = label
        self.mode, self.reason = resolve_shard_rules(mesh, label=label)
        self._mesh = mesh if self.mode in ("rules", "replicate") else None
        self._dp = (axis_size(self._mesh, DATA_AXIS)
                    if self.mode == "rules" else 1)
        self._ladder = bucket_ladder(max(int(max_batch), 1))
        self._length_ladder = (length_ladder(int(max_length))
                               if max_length else None)
        self._seen_rungs: set = set()
        self._staging: Dict[str, list] = {}   # a row source's, by column
        self.autocast = resolve_infer_autocast()
        dtype = param_dtype
        if dtype is None and self.autocast == "bf16":
            import jax.numpy as jnp
            dtype = jnp.bfloat16
        if params is not None:
            specs = match_partition_rules(
                FAMILY_RULES[family], params, mesh=self._mesh,
                label=f"{family}:{label}")
            shard_fns, _ = make_shard_and_gather_fns(
                specs, mesh=self._mesh, dtype_specs=dtype)
            self._params = jax.tree_util.tree_map(
                lambda f, x: f(x), shard_fns, params)
            donate = (1,) if jax.default_backend() != "cpu" else ()
            self._call = (jax.jit(lambda p, x: apply_fn(p, x),
                                  donate_argnums=donate)
                          if jit else apply_fn)
        else:
            self._params = None
            self._call = apply_fn  # caller supplies a jitted closure

    # -- dispatch ------------------------------------------------------

    def _rung(self, n: int) -> int:
        from mmlspark_tpu.parallel.inference import bucket_for

        return bucket_for(max(n, 1), self._ladder)

    def padded_rows(self, n: int) -> int:
        """The rows a batch of ``n`` is padded to: ``dp x`` its rung."""
        return self._dp * self._rung(n)

    def length_batches(self, lengths) -> List[Tuple[np.ndarray, int]]:
        """``[(row indices, length rung)]``: the device batches of a
        ragged column (``parallel/inference.length_batches``), ``dp x``
        the top row rung rows each."""
        from mmlspark_tpu.parallel.inference import length_batches

        if self._length_ladder is None:
            raise ValueError("this scorer was built without max_length")
        return length_batches(np.asarray(lengths),
                              self._dp * self._ladder[-1],
                              self._length_ladder)

    def _row_sharding(self, ndim: int):
        import jax

        spec = [None] * ndim
        if self.mode == "rules":
            spec[0] = DATA_AXIS
        return jax.sharding.NamedSharding(
            self._mesh, jax.sharding.PartitionSpec(*spec))

    def _put(self, group: Dict[str, Any]) -> Dict[str, Any]:
        """One group's columns on the device. The ndarrays take one
        ``device_put`` each, all under one span ``scorer.put``
        (``chunks`` 1). A row source goes through
        ``ops/ingest.chunked_device_put``: over a chunk's bytes its rows
        are laid out and put a chunk at a time (``chunks`` > 1 on each
        chunk's span), in staging buffers this scorer keeps from call
        to call; under them, laid out whole and put once."""
        import jax

        from mmlspark_tpu.ops.ingest import (STAGING_DEPTH,
                                             chunked_device_put)

        def sharding(ndim):
            return (self._row_sharding(ndim) if self._mesh is not None
                    else None)

        arrays = {k: v for k, v in group.items()
                  if isinstance(v, np.ndarray)}
        placed = {}
        if arrays:
            with span("scorer.put", chunks=1,
                      bytes=sum(v.nbytes for v in arrays.values())):
                for k, v in arrays.items():
                    placed[k] = jax.device_put(v, sharding(v.ndim))
        for k in group.keys() - arrays.keys():
            # checked out, so that two callers never fill one buffer
            ring = self._staging.pop(k, None) or [None] * STAGING_DEPTH
            try:
                placed[k] = chunked_device_put(
                    group[k], sharding(group[k].ndim),
                    row_multiple=self._dp, span_name="scorer.put",
                    staging=ring)
            finally:
                self._staging[k] = ring
        return placed

    def _dispatch(self, group):
        if self._params is not None:
            return self._call(self._params, group)
        return self._call(group)

    def __call__(self, x):
        """Score rows; returns host numpy with the same tree structure
        as ``apply_fn``'s output, batch-dim outputs sliced to the true
        row count.

        A column may be an ``ops/ingest.RowSource`` (an object column
        as it is) in place of an ndarray: a group of it over one chunk's
        bytes is fed to the device a chunk at a time (``_put``).

        Spans (core/timer.py), each group of ``dp x rung`` rows:
        ``scorer.pad`` (slice, zero-fill to the rung), ``scorer.put``
        and ``scorer.dispatch``; then one ``scorer.fetch``. Put and
        dispatch are asynchronous, so their spans time the host's part
        of each (layout and enqueue) and nothing waits for the device
        until the fetch: ``scorer.fetch`` holds the wait for the copies
        and the program as well as the copy back and the concatenate.
        A chunked feed differs: each chunk has a span of the row
        source's own name (its lay-out, the wait for the workers that
        do it included) and a ``scorer.put``, whose ``chunks`` says
        how many the group took (1: a single put), and the last
        ``scorer.put`` waits until the chunks have landed and are one
        array, so ``scorer.fetch`` there waits for the program and the
        copy back alone.
        The profiler's trace, on the same clock, is what splits it into
        device-busy and device-idle.
        """
        import jax

        from mmlspark_tpu.core import sanitizer
        from mmlspark_tpu.ops.ingest import RowSource

        is_dict = isinstance(x, dict)
        cols = {k: v if isinstance(v, RowSource) else np.asarray(v)
                for k, v in (x.items() if is_dict else [("__x__", x)])}
        n = next(iter(cols.values())).shape[0]
        r = self._rung(n)
        step = self._dp * r
        # one program a row rung; with a length ladder, a (row rung,
        # length rung), the length being the batch's second dimension
        seen = r if self._length_ladder is None else (
            r, max(v.shape[1] for v in cols.values() if v.ndim > 1))
        if seen not in self._seen_rungs:
            self._seen_rungs.add(seen)
            sanitizer.count_recompile(
                f"shard_rules {self.family}:{self.label} rung {seen} "
                f"(global {step})")
        chunks = []
        for g in range(0, max(n, 1), step):
            with span("scorer.pad", rows=step):
                group = {}
                for k, v in cols.items():
                    if isinstance(v, RowSource):
                        group[k] = v.window(g, step)
                        continue
                    gv = v[g:g + step]
                    if gv.shape[0] < step:
                        fill = np.zeros(
                            (step - gv.shape[0],) + gv.shape[1:],
                            dtype=gv.dtype)
                        gv = np.concatenate([gv, fill]) if gv.shape[0] \
                            else fill
                    group[k] = gv
            group = self._put(group)
            with span("scorer.dispatch"):
                chunks.append(self._dispatch(
                    group if is_dict else group["__x__"]))

        def fetch(a):
            if getattr(a, "is_fully_addressable", True):
                return np.asarray(jax.device_get(a))
            # process-spanning mesh (multi-host): the global value
            # is not locally addressable; allgather it to every host
            from jax.experimental import multihost_utils
            return np.asarray(
                multihost_utils.process_allgather(a, tiled=True))

        with span("scorer.fetch") as fetched:
            flat0, treedef = jax.tree_util.tree_flatten(chunks[0])
            gathered = []
            for i in range(len(flat0)):
                leaves = [fetch(jax.tree_util.tree_flatten(c)[0][i])
                          for c in chunks]
                a = leaves[0]
                if a.ndim >= 1 and a.shape[0] == step:
                    gathered.append(np.concatenate(leaves)[:n])
                else:
                    gathered.append(a)  # non-batch output: first chunk's
            fetched.counts["bytes"] = sum(a.nbytes for a in gathered)
        return jax.tree_util.tree_unflatten(treedef, gathered)

    # -- metadata ------------------------------------------------------

    def metadata(self) -> Dict[str, Any]:
        return {"shard_rules": self.mode,
                "shard_rules_reason": self.reason,
                "shard_rules_family": self.family,
                "infer_autocast": self.autocast,
                "shard_rules_dp": self._dp}
