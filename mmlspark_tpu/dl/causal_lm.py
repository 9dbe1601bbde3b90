"""Causal language model as a transformer stage: a column of prompts
in, a column of greedy completions (and their log-probabilities) out.

Parity: SynapseML's ``HuggingFaceCausalLM`` (a ``Transformer`` from a
prompt column to a completion column, batched generation behind it).
The model is :class:`~mmlspark_tpu.dl.backbones.HybridLM` built from
``modelConfig``, the model's ``config.json`` as a dict, whose
``model_type`` names the family (``dl/backbones.py`` documents each
family's keys). The stage uses its contract alone: ``init_state``, the
``hidden``/``head`` methods, ``hidden_in_groups`` and its groups of
rows, and ``counts``, what a state says its rows went through. A device
batch is sized by the bytes of the state (``lm_state_bytes``): its
fixed part, and the part that grows with the longest prompt plus
``maxNewTokens``.

One ``transform()``: the ragged prompts are sorted by length and cut
into device batches (``ShardedScorer.length_batches``: the row ladder
and the length ladder), each batch padded to its rungs (``lm.stack``)
and scored by the shared engine, which pads rows, places, dispatches
and fetches (``scorer.*``). A batch runs two programs: ``lm_prefill``
absorbs the prompt a chunk of tokens at a time with the state as the
scan's carry; ``lm_generate`` takes that state donated and decodes
``maxNewTokens`` greedy tokens in one ``lax.scan``. Padding never
touches the state, writes nothing to a cache (whose rows are indexed by
each row's own position) and is masked out of every softmax, so a
row's output does not depend on its rungs or its neighbours.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from mmlspark_tpu.core import scopes
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.param import (
    HasInputCol, HasOutputCol, Param, gt, to_bool, to_int, to_str,
)
from mmlspark_tpu.core.pipeline import Transformer
from mmlspark_tpu.core.timer import current_span, span


def _free_device_bytes() -> Optional[int]:
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def prefill_steps(prefill_chunk: int, length: int):
    """``(chunk, steps)``: the tokens a row a prefill step absorbs and
    the steps a length rung takes."""
    chunk = min(prefill_chunk, length)
    return chunk, -(-length // chunk)


def lm_prefill_program(module, prefill_chunk: int, new_tokens: int):
    """``lm_prefill(params, ids, lengths) -> (hidden after each row's
    last prompt token, state)``: the prompt absorbed ``prefill_chunk``
    tokens a row at a time, the state the scan's carry. A cache in it
    has room for the batch's length rung (or its whole steps, where the
    chunk does not divide the rung: a step's stretch is written whole)
    and ``new_tokens`` more."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl.backbones import HybridLM, lm_init_state

    config = module.config

    def lm_prefill(params, ids, lengths):
        rows, length = ids.shape
        chunk, steps = prefill_steps(prefill_chunk, length)
        ids = jnp.pad(ids, ((0, 0), (0, steps * chunk - length)))
        ids = jnp.moveaxis(ids.reshape(rows, steps, chunk), 1, 0)

        def step(carry, xs):
            state, last = carry
            chunk_ids, start = xs
            real = jnp.clip(lengths - start, 0, chunk)
            h, state = HybridLM.hidden_in_groups(module, params, chunk_ids,
                                                 real, state)
            with jax.named_scope("lm.last"):
                last = jnp.where((real > 0)[:, None], h, last)
            return (state, last), None

        first = (lm_init_state(config, rows, steps * chunk + new_tokens),
                 jnp.zeros((rows, config["hidden_size"]), jnp.float32))
        (state, last), _ = jax.lax.scan(
            step, first, (ids, jnp.arange(steps) * chunk))
        return last, state

    return lm_prefill


def lm_generate_program(module, new_tokens: int, with_logits: bool):
    """``lm_generate(params, last, state)``: ``new_tokens`` greedy
    tokens a row in one scan, the state its carry: ``({"tokens",
    "logprobs"[, "logits"]} and the model's ``counts`` of the final
    state, state)``."""
    import jax
    import jax.numpy as jnp

    def emit(logits):
        with jax.named_scope("lm.sample"):
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            chosen = jnp.take_along_axis(logits, token[:, None], axis=1)
            logprob = chosen[:, 0] - jax.nn.logsumexp(logits, axis=-1)
        out = {"tokens": token, "logprobs": logprob}
        return dict(out, logits=logits) if with_logits else out

    def lm_generate(params, last, state):
        def step(carry, _):
            logits, state = carry
            out = emit(logits)
            token = out["tokens"]
            logits, state = module.apply(
                params, token[:, None], jnp.ones_like(token), state)
            return (logits, state), out

        logits = module.apply(params, last, method="head")
        (logits, state), outs = jax.lax.scan(
            step, (logits, state), None, length=new_tokens - 1)
        final = emit(logits)
        outs = {k: jnp.concatenate([jnp.moveaxis(v, 0, 1),
                                    final[k][:, None]], axis=1)
                for k, v in outs.items()}
        outs.update(module.counts(module.config, state))
        # the state goes out again so that the donated buffers have an
        # output to alias: the scan then updates them in place, and a
        # second copy of the state (which would not fit) is never made
        return outs, state

    return lm_generate


class CausalLM(Transformer, HasInputCol, HasOutputCol):
    modelConfig = Param("modelConfig", "the model's config.json as a dict; "
                        "model_type names its family, whose keys "
                        "dl/backbones.py documents", is_complex=True)
    maxNewTokens = Param("maxNewTokens", "tokens generated a row (greedy, "
                         "no early stop)", to_int, gt(0), default=32)
    batchSize = Param("batchSize", "rows a device batch; unset, the "
                      "largest power of two whose state and cache (at "
                      "maxLength's rung plus maxNewTokens) fit half the "
                      "device's free memory", to_int, gt(0))
    maxLength = Param("maxLength", "longest prompt in tokens (longer ones "
                      "keep their last maxLength tokens)", to_int, gt(0),
                      default=1024)
    prefillChunk = Param("prefillChunk", "tokens a row absorbed a prefill "
                         "step", to_int, gt(0), default=128)
    seed = Param("seed", "seed of the weights when none are given",
                 to_int, default=0)
    logProbsCol = Param("logProbsCol", "output column of the generated "
                        "tokens' log-probabilities", to_str,
                        default="logprobs")
    logitsCol = Param("logitsCol", "output column of every generated "
                      "position's logits (maxNewTokens x vocab a row; "
                      "unset: not returned)", to_str)
    allowRandomWeights = Param(
        "allowRandomWeights", "explicitly allow seeded random weights "
        "(completions then carry NO meaning)", to_bool, default=False)

    _weights = None          # the model's parameter pytree, when given
    _module = None
    _scorer = None
    _programs = None         # the module's jitted programs by setting

    def set_weights(self, params) -> "CausalLM":
        """Use this parameter pytree (the structure of
        ``backbones.lm_param_shapes(modelConfig)``)."""
        self._weights = params
        self._scorer = None
        return self

    # -- the model -----------------------------------------------------

    def _config(self) -> dict:
        config = self.get("modelConfig")
        if not config:
            raise ValueError("CausalLM needs modelConfig: the model's "
                             "config.json as a dict")
        return dict(config)

    def _ensure_weights(self):
        from mmlspark_tpu.dl.backbones import lm_init_params

        if self._weights is None:
            if not self.get("allowRandomWeights"):
                raise ValueError(
                    "CausalLM has no weights: give it a parameter pytree "
                    "with set_weights(params), load a saved stage, or opt "
                    "in to seeded random weights with "
                    "allowRandomWeights=True (completions then carry NO "
                    "meaning)")
            self._weights = lm_init_params(self._config(),
                                           self.get("seed"))
        return self._weights

    def _batch_rows(self) -> int:
        from mmlspark_tpu.dl.backbones import lm_state_bytes

        if self.is_set("batchSize"):
            return self.get("batchSize")
        free = _free_device_bytes()
        if free is None:                     # no memory statistics: the CPU
            return 8
        from mmlspark_tpu.parallel.inference import length_ladder

        capacity = (length_ladder(self.get("maxLength"))[-1]
                    + self.get("maxNewTokens"))
        rows = 1
        while (rows < 1024 and sum(lm_state_bytes(
                self._config(), rows * 2, capacity).values()) <= free // 2):
            rows *= 2
        return rows

    def _ensure_scorer(self):
        from mmlspark_tpu.dl.backbones import lm_dtype, lm_module
        from mmlspark_tpu.parallel.shard_rules import ShardedScorer

        if self._scorer is None:
            config = self._config()
            self._module = lm_module(config)
            self._programs = {}     # they close over the module
            self._scorer = ShardedScorer(
                self._generate, self._ensure_weights(), family="dl",
                max_batch=self._batch_rows(), label="causal_lm",
                param_dtype=lm_dtype(config),
                max_length=self.get("maxLength"), jit=False)
        return self._scorer

    def _program(self, name: str, with_logits: bool = False):
        """The two jitted programs of a device batch, built once a
        setting: ``lm_prefill`` and ``lm_generate`` (their names are what
        a profiler's module line shows), and again for a new module
        (``_ensure_scorer``). Ids, the last hidden state and the model's
        state are donated. A program closes over the module
        and numbers, not over this stage or its weights, so that
        ``core.scopes`` may keep it past the stage's life."""
        import jax

        programs = self._programs
        new, chunk = self.get("maxNewTokens"), self.get("prefillChunk")
        key = (name, with_logits, new, chunk)
        if key not in programs:
            fn = (lm_prefill_program(self._module, chunk, new)
                  if name == "lm_prefill" else
                  lm_generate_program(self._module, new, with_logits))
            donate = () if jax.default_backend() == "cpu" else (1, 2)
            programs[key] = jax.jit(fn, donate_argnums=donate)
        return programs[key]

    def _prefill_visits(self, lengths: np.ndarray, length: int):
        """``[visits, run]`` of one ``lm_prefill`` over these rows'
        lengths (the device batch's, zero rows included) at a length
        rung: a visit is a group of rows in a step
        (``HybridLM.row_groups``: a step of one group is one visit,
        run); those run are the ones inside the bounds of the group
        loop (``HybridLM.active_groups``)."""
        from mmlspark_tpu.dl.backbones import HybridLM

        chunk, steps = prefill_steps(self.get("prefillChunk"), length)
        groups = HybridLM.row_groups(len(lengths), chunk)
        if groups == 1:
            return np.array([steps, steps])
        run = 0
        for start in np.arange(steps) * chunk:
            first, stop = HybridLM.active_groups(
                np.clip(lengths - start, 0, chunk), groups)
            run += int(stop - first)
        return np.array([groups * steps, run])

    def _generate(self, params, batch):
        """What the engine calls a device batch with: two programs,
        each registered with ``core.scopes`` before it takes its
        donated arguments."""
        prefill = self._program("lm_prefill")
        scopes.register(prefill, params, batch["ids"], batch["lengths"])
        last, state = prefill(params, batch["ids"], batch["lengths"])
        generate = self._program("lm_generate", self.is_set("logitsCol"))
        scopes.register(generate, params, last, state)
        out, _ = generate(params, last, state)
        return out

    # -- the stage -----------------------------------------------------

    def _prompts(self, dataset: DataFrame):
        col = dataset.col(self.get("inputCol"))
        config = self._config()
        if len(col) and isinstance(col[0], str):
            from mmlspark_tpu.dl.text import hash_tokenize
            ids = hash_tokenize([str(v) for v in col], self.get("maxLength"),
                                config["vocab_size"])
            return [row[:max(int((row > 0).sum()), 1)] for row in ids]
        return [np.asarray(v, np.int32).reshape(-1)[-self.get("maxLength"):]
                for v in col]

    def _transform(self, dataset: DataFrame) -> DataFrame:
        from mmlspark_tpu.dl.backbones import lm_state_bytes

        scorer = self._ensure_scorer()
        new = self.get("maxNewTokens")
        root = current_span()
        with span("lm.stack", rows=dataset.num_rows) as stack:
            prompts = self._prompts(dataset)
            lengths = np.array([len(p) for p in prompts], np.int32)
            batches = []
            for index, rung in scorer.length_batches(lengths):
                ids = np.zeros((len(index), rung), np.int32)
                for row, i in enumerate(index):
                    ids[row, :lengths[i]] = prompts[i]
                batches.append((index, {"ids": ids,
                                        "lengths": lengths[index]}))
            padded = sum(b["ids"].size for _, b in batches)
            stack.counts.update(prompt_tokens=int(lengths.sum()),
                                padded_tokens=int(padded))
        outputs = [(index, scorer(batch)) for index, batch in batches]
        with span("lm.columns"):
            out = dataset
            names = {"tokens": self.get("outputCol"),
                     "logprobs": self.get("logProbsCol"),
                     "logits": self.get("logitsCol")}
            for key, name in names.items():
                if not outputs or key not in outputs[0][1]:
                    continue
                first = outputs[0][1][key]
                column = np.zeros((len(prompts),) + first.shape[1:],
                                  first.dtype)
                for index, scored in outputs:
                    column[index] = scored[key]
                if column.ndim > 2:          # ragged-safe object column
                    boxed = np.empty(len(column), dtype=object)
                    for i in range(len(column)):
                        boxed[i] = column[i]
                    column = boxed
                out = out.with_column(name, column)
        if root is not None:
            rows = max((len(index) for index, _ in batches), default=0)
            rung = max((b["ids"].shape[1] for _, b in batches), default=0)
            held = lm_state_bytes(self._config(), rows, rung + new)
            visits = sum((self._prefill_visits(
                np.pad(b["lengths"], (0, scorer.padded_rows(len(index))
                                      - len(index))), b["ids"].shape[1])
                for index, b in batches), np.zeros(2, int))
            root.counts.update(
                new_tokens=int(new * len(prompts)),
                state_bytes=int(held["state"]),
                cache_bytes=int(held["cache"]), length_rung=int(rung),
                prefill_visits=int(visits[0]),
                prefill_visits_run=int(visits[1]))
            # what the model counted on the device (HybridLM.counts),
            # summed over the batches
            counted = [key for key in (outputs[0][1] if outputs else ())
                       if key not in names]
            for key in counted:
                root.counts[key] = int(sum(np.sum(scored[key])
                                           for _, scored in outputs))
            if "expert_pairs" in counted:
                root.counts["expert_pairs_max"] = int(sum(
                    scored["expert_pairs"] for _, scored in outputs).max())
        return out

    # -- persistence (as DeepModel: leaves in order) -------------------

    def _get_state(self):
        import jax

        flat, _ = jax.tree_util.tree_flatten(self._ensure_scorer()._params)
        return {f"p{i}": np.asarray(v, np.float32)
                for i, v in enumerate(flat)}

    def _set_state(self, state):
        import jax

        from mmlspark_tpu.dl.backbones import lm_param_shapes

        shapes = lm_param_shapes(self._config())
        flat, treedef = jax.tree_util.tree_flatten(shapes)
        self._weights = jax.tree_util.tree_unflatten(
            treedef, [state[f"p{i}"] for i in range(len(flat))])
        self._scorer = None
