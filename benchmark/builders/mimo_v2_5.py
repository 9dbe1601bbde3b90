"""MiMo-V2.5's cut as a ``CausalLM`` stage: weights and prompts from the
seed.

Weights are ``builders/kimi_k2.py``'s scheme (made on the device, one
key a tensor from the seed and the tensor's name, every tensor in the
dtype the configuration states): matrices normal at 0.02, the
embedding at 1.0, norm scales about 0.8-1.2, the router normal at 0.5
over the root of the hidden size with a selection bias normal at 0.02.
A window layer's sink logits are that scheme's draw moved to about 5,
spread by 1: at these widths a query's scores over its 128 window
positions sum to about ``exp(6)``, so the sink takes a share of the
weights that differs from head to head and no fault in it can hide.

Prompts (``make_frames``) are drawn stratified from the cell's
log-normal. In the first frame the shortest prompt past ``CHECK_PAST``
tokens is moved to the front and the shortest of all second, so that
the two rows a run checks against the reference are the frame's two
shortest (8,192 tokens and a little more: their decode steps read full
layers' caches of 8-10k positions among rows of up to 32k, the window
layers' rings have turned some seventy times) and the reference, which
runs both rows at the longer one's length, fits the run's time (on a
TPU v5e, rows of 17.5k and 8.2k tokens checked at 18k positions took
190 s of a 417 s run).

``reference_weights`` is the same arrays under
``reference/mimo_v2_5.py``'s names.
"""

import math
import statistics

import numpy as np

from benchmark.lookup import load_module

_kimi = load_module("builders", "kimi_k2")
CHECK_PAST = 8192
SINK = (5.0, 1.0)      # centre and spread of the sink logits
DENSE_NAMES = {"gate_proj": "w_gate", "up_proj": "w_up",
               "down_proj": "w_down"}


def make_frames(seed, frames, rows, traffic, vocab):
    """``frames`` object columns of ``rows`` int32 prompts each: lengths
    log-normal (``length_median``, ``length_sigma``) clipped to
    ``[length_min, length_max]``, one draw from each of ``rows`` equal
    slices of the distribution a frame (in a seeded order), so that a
    call's cost, which grows with the square of its rows' lengths, does
    not swing with the seed; ids uniform over the vocabulary."""
    children = np.random.SeedSequence([seed, 2]).spawn(frames)
    normal = statistics.NormalDist(math.log(traffic["length_median"]),
                                   traffic["length_sigma"])
    out = []
    for child in children:
        rng = np.random.default_rng(child)
        quantiles = (np.arange(rows) + rng.uniform(size=rows)) / rows
        lengths = np.clip(
            np.rint(np.exp([normal.inv_cdf(q) for q in quantiles])),
            traffic["length_min"], traffic["length_max"]).astype(int)
        col = np.empty(rows, dtype=object)
        for i, n in enumerate(lengths[rng.permutation(rows)]):
            col[i] = rng.integers(0, vocab, n, dtype=np.int32)
        out.append(col)
    lengths = np.array([len(p) for p in out[0]])
    past = np.flatnonzero(lengths > traffic.get("check_past", CHECK_PAST))
    first = (past[np.argmin(lengths[past])] if len(past)
             else int(np.argmax(lengths)))
    order = [int(first), int(np.argmin(lengths))]
    out[0] = out[0][order + [i for i in range(rows) if i not in order]]
    return out


def make_weights(seed, model_config):
    """The stage's parameter pytree (``backbones.lm_param_shapes``)."""
    import jax
    import jax.numpy as jnp

    def place(path, leaf):
        if str(getattr(path[-1], "key", path[-1])) != "sink":
            return leaf
        centre, spread = SINK                  # vectors are drawn at 0.2
        return (centre + leaf.astype(jnp.float32) * (spread / 0.2)).astype(
            leaf.dtype)

    return jax.tree_util.tree_map_with_path(
        place, _kimi.make_weights(seed, model_config))


def reference_weights(params, model_config):
    """The same arrays under ``reference/mimo_v2_5.py``'s names."""
    p = params["params"]
    layers = []
    for i in range(model_config["num_hidden_layers"]):
        m = p[f"layers_{i}"]
        mixer = {"w_qkv": m["mixer"]["qkv_proj"]["kernel"],
                 "wo": m["mixer"]["o_proj"]["kernel"]}
        if "sink" in m["mixer"]:
            mixer["sink"] = m["mixer"]["sink"]
        ffn = {DENSE_NAMES.get(k, k): v for k, v in m["ffn"].items()}
        layers.append({"mixer": mixer, "ffn": ffn,
                       "attn_norm": m["mixer_pre"], "ffn_norm": m["ffn_pre"]})
    return {"embed": p["embedding"], "layers": layers,
            "final_norm": p["final_norm"], "head": p["lm_head"]["kernel"]}


def build(ctx):
    import jax

    from mmlspark_tpu.dl.causal_lm import CausalLM

    cfg = ctx.config
    model_config = {k: cfg[k] for k in cfg["model_keys"]}
    params = make_weights(ctx.seed, model_config)
    model = CausalLM(inputCol="prompt", outputCol="completion",
                     modelConfig=model_config,
                     maxNewTokens=ctx.cell["traffic"]["new_tokens"],
                     batchSize=cfg["batchSize"], maxLength=cfg["maxLength"],
                     prefillChunk=cfg["prefillChunk"]).set_weights(params)
    return {"model": model, "model_config": model_config,
            "weights": reference_weights(params, model_config),
            "parameters": int(sum(x.size for x in
                                  jax.tree_util.tree_leaves(params)))}
