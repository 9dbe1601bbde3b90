"""Motif-3-Beta's cut as a ``CausalLM`` stage: weights and prompts from
the seed.

Weights are ``builders/xing4_0.py``'s (made on the device, one key a
tensor from the seed and the tensor's name, every tensor in the dtype
the configuration states; matrices normal at 0.02, the embedding at
1.0, norm scales about 0.8-1.2, the router normal at 0.5 over the root
of the hidden size, the residual path's leaves off the trivial). The
PolyNorm leaves are that scheme's draws moved off the trivial too, so
that no fault in the activation can hide: the three weights about a
third each, spread by 0.15 (no power weighs nothing), the bias about
0.8, spread by 0.3, so that the clamp at 0.5 holds most of them.
``lambda_proj`` is a matrix at 0.02: ``x W_lambda`` over 4,096 normed
inputs spreads by about 1.3, so lambda runs from about 0.2 to 0.8 by
token and head.

Prompts (``make_frames``) are drawn stratified from the cell's
log-normal. In the first frame the shortest prompt past ``CHECK_PAST``
tokens is moved to the front and the shortest of all second, so that
the two rows a run checks against the reference hold one past 8,192
tokens (the window layers' ring has turned some sixty times and YaRN's
scaled range is reached) and the reference, which runs both rows at
the longer one's length, is no longer than that needs.

``reference_weights`` is the same arrays under ``reference/motif3.py``'s
names.
"""

import math
import statistics

import numpy as np

from benchmark.lookup import load_module

_xing = load_module("builders", "xing4_0")
CHECK_PAST = 8192

MIXER_NAMES = {"q_a_proj": "w_dq", "q_b_proj": "w_uq", "kv_a_proj": "w_dkv",
               "o_proj": "wo", "q_a_norm": "q_norm", "kv_a_norm": "kv_norm",
               "kv_b_proj": "w_ukv", "lambda_proj": "w_lambda",
               "g_proj": "w_gate"}
DENSE_NAMES = {"gate_proj": "w_gate", "up_proj": "w_up",
               "down_proj": "w_down"}
# a PolyNorm leaf: (centre, spread) of the moved draw
POLY = {"poly_weight": (1 / 3, 0.15), "poly_bias": (0.8, 0.3)}


def make_frames(seed, frames, rows, traffic, vocab):
    """``frames`` object columns of ``rows`` int32 prompts each: lengths
    log-normal (``length_median``, ``length_sigma``) clipped to
    ``[length_min, length_max]``, drawn stratified, one draw from each
    of ``rows`` equal slices of the distribution a frame (in a seeded
    order), so that every frame is the distribution's sample and a
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
    past = np.flatnonzero(lengths > CHECK_PAST)
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
        name = str(getattr(path[-1], "key", path[-1]))
        tail = next((k for k in POLY if name.endswith(k)), None)
        if tail is None:
            return leaf
        centre, spread = POLY[tail]
        draw = leaf.astype(jnp.float32)          # vectors at 0.2, stacks 0.02
        std = 0.2 if leaf.ndim == 1 else 0.02
        return (centre + draw * (spread / std)).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(
        place, _xing.make_weights(seed, model_config))


def reference_weights(params, model_config):
    """The same arrays under ``reference/motif3.py``'s names."""
    p = params["params"]
    layers = []
    for i in range(model_config["num_hidden_layers"]):
        m = p[f"layers_{i}"]
        mixer = {MIXER_NAMES[k]: v["kernel"] if isinstance(v, dict) else v
                 for k, v in m["mixer"].items()}
        ffn = {DENSE_NAMES.get(k, k): v for k, v in m["ffn"].items()}
        layers.append({"mixer": mixer, "ffn": ffn,
                       "attn_norm": m["mixer_pre"], "ffn_norm": m["ffn_pre"],
                       "mixer_hc": m["mixer_hc"], "ffn_hc": m["ffn_hc"]})
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
