"""Brumby-14B-Base as a ``CausalLM`` stage: weights and prompts from
the seed.

Weights are made on the device, one key a tensor from the seed and the
tensor's name (so a tensor does not depend on how many others there
are), every tensor in the dtype the configuration states, and handed to the stage as
its parameter pytree; ``reference_weights`` is the same arrays under
the plain reference's names. Matrices are normal at 0.02 (the
initialiser range of the checkpoint's family); norm scales are 1 + 0.1
normal, so that a fault in a norm cannot hide; the gate's matrix is
normal at 0.004 and its bias runs from 4.0 to 6.5 over the key-value
heads, which puts ``g`` in about 0.96-0.999: the state then carries
hundreds of tokens, and a wrong state shows in the logits.
"""

import functools
import zlib

import numpy as np

SCALES = {"g_proj/kernel": 0.004}


def _key(seed, name):
    import jax

    state = np.random.SeedSequence(
        [int(seed), zlib.crc32(name.encode())]).generate_state(1)
    return jax.random.PRNGKey(int(state[0]) >> 1)


def make_weights(seed, model_config):
    """The stage's parameter pytree (``backbones.lm_param_shapes``)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl.backbones import lm_dtype, lm_param_shapes
    from mmlspark_tpu.parallel.shard_rules import _leaf_paths

    kv = model_config["num_key_value_heads"]
    dtype = lm_dtype(model_config)

    @functools.partial(jax.jit, static_argnums=(1,))
    def matrix(key, like, std):
        return (jax.random.normal(key, like.shape, jnp.float32)
                * std).astype(like.dtype)

    def make(name, like):
        key = _key(seed, name)
        # vectors too are made in the dtype the model states: the engine
        # places every float leaf in it, and the reference has to be
        # given the values the program computes with
        if name.endswith("g_bias"):
            return jnp.linspace(4.0, 6.5, kv, dtype=jnp.float32).astype(dtype)
        if len(like.shape) == 1:
            return (1.0 + 0.1 * jax.random.normal(
                key, like.shape, jnp.float32)).astype(dtype)
        std = next((s for tail, s in SCALES.items() if name.endswith(tail)),
                   0.02)
        return matrix(key, like, std)

    shapes = lm_param_shapes(model_config)
    names = [name for name, _ in _leaf_paths(shapes)]
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [make(n, s) for n, s in zip(names, leaves)])


def reference_weights(params, model_config):
    """The same arrays under ``reference/brumby.py``'s names."""
    p = params["params"]
    layers = []
    for i in range(model_config["num_hidden_layers"]):
        m = p[f"layers_{i}"]
        layers.append({
            "attn_norm": m["attn_norm"], "wq": m["q_proj"]["kernel"],
            "wk": m["k_proj"]["kernel"], "wv": m["v_proj"]["kernel"],
            "wg": m["g_proj"]["kernel"], "bg": m["g_bias"],
            "q_norm": m["q_norm"], "k_norm": m["k_norm"],
            "wo": m["o_proj"]["kernel"], "mlp_norm": m["mlp_norm"],
            "w_gate": m["gate_proj"]["kernel"],
            "w_up": m["up_proj"]["kernel"],
            "w_down": m["down_proj"]["kernel"]})
    return {"embed": p["embedding"], "layers": layers,
            "final_norm": p["final_norm"], "head": p["lm_head"]["kernel"]}


def make_frames(seed, frames, rows, traffic, vocab):
    """``frames`` object columns of ``rows`` int32 prompts each: lengths
    log-normal (``length_median``, ``length_sigma``) clipped to
    ``[length_min, length_max]``, ids uniform over the vocabulary."""
    children = np.random.SeedSequence([seed, 2]).spawn(frames)
    out = []
    for child in children:
        rng = np.random.default_rng(child)
        lengths = np.clip(
            np.rint(rng.lognormal(np.log(traffic["length_median"]),
                                  traffic["length_sigma"], rows)),
            traffic["length_min"], traffic["length_max"]).astype(int)
        col = np.empty(rows, dtype=object)
        for i, n in enumerate(lengths):
            col[i] = rng.integers(0, vocab, n, dtype=np.int32)
        out.append(col)
    return out


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
