"""GigaChat3.5-432B-A28B's cut as a ``CausalLM`` stage: weights and
prompts from the seed.

Weights are made on the device, one key a tensor from the seed and the
tensor's name, every tensor in the dtype the configuration states
(``builders/brumby_14b.py``'s scheme), and handed to the stage as its
parameter pytree; ``reference_weights`` is the same arrays under the
plain reference's names. Each family is seeded so that no fault can
hide:

- matrices normal at 0.02; the embedding at 1.0, so that the tokens
  weigh as much in the residual as a sub-layer's normed output;
- norm weights (stored centred at zero) normal at 0.2: scales of about
  0.8-1.2, off 1;
- the delta rule's ``dt_bias`` runs from -7 to -4 and ``A_log`` from
  -0.5 to 0.5 over the value heads, ``W_a`` normal at 0.004: decays of
  about 0.98-0.9995 a token, so the state carries hundreds of tokens
  and a wrong state shows in the logits; convolution taps normal at 0.5;
- the router normal at 0.5 over the root of the hidden size (0.006 as
  published: scores of about 0.35-0.7, spread, so the chosen eight's
  weights differ and every token's eight fall anywhere in the 256) with
  a selection bias normal at 0.02.
"""

import functools
import math

from benchmark.lookup import load_module

_brumby = load_module("builders", "brumby_14b")
make_frames = _brumby.make_frames

STD = {"embedding": 1.0, "a_proj/kernel": 0.004, "router_bias": 0.02,
       "conv": 0.5}
RAMPS = {"dt_bias": (-7.0, -4.0), "A_log": (-0.5, 0.5)}


BLOCKS = (1 << 16, 1 << 24)   # standard normals a launch: small, large


def make_weights(seed, model_config):
    """The stage's parameter pytree (``backbones.lm_param_shapes``).
    Every tensor is cut from blocks of standard normals made by one
    compiled generator a block size (the tensor's key folded with the
    block's index; the small size for tensors that fit one) and then
    scaled, shaped and cast: a generator compiled a shape costs 1.6 s
    each of 24 shapes before the first call of a cold run, the cut a
    third of that."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl.backbones import lm_param_shapes
    from mmlspark_tpu.parallel.shard_rules import _leaf_paths

    @functools.partial(jax.jit, static_argnums=(2,))
    def block(key, index, size):
        return jax.random.normal(jax.random.fold_in(key, index), (size,),
                                 jnp.float32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def cut(blocks, like, std):
        flat = jnp.concatenate(blocks)[:math.prod(like.shape)]
        return (flat.reshape(like.shape) * std).astype(like.dtype)

    def make(name, like):
        tail = name.rsplit("/", 1)[-1]
        if tail in RAMPS:
            return jnp.linspace(*RAMPS[tail], like.shape[0],
                                dtype=jnp.float32).astype(like.dtype)
        std = next((s for end, s in STD.items() if name.endswith(end)),
                   0.2 if len(like.shape) == 1 else 0.02)
        if tail == "router":
            std = 0.5 * like.shape[0] ** -0.5
        key, n = _brumby._key(seed, name), math.prod(like.shape)
        size = BLOCKS[n > BLOCKS[0]]
        return cut([block(key, i, size) for i in range(-(-n // size))],
                   like, std)

    shapes = lm_param_shapes(model_config)
    names = [name for name, _ in _leaf_paths(shapes)]
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    # vectors too in the dtype the model states: the engine places every
    # float leaf in it, and the reference has to be given those values
    dtype = shapes["params"]["embedding"].dtype
    leaves = [jax.ShapeDtypeStruct(x.shape, dtype) for x in leaves]
    return jax.tree_util.tree_unflatten(
        treedef, [make(n, s) for n, s in zip(names, leaves)])


MIXER_NAMES = {
    "q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "z_proj": "wz",
    "b_proj": "wb", "a_proj": "wa", "o_proj": "wo", "q_a_proj": "w_dq",
    "q_b_proj": "w_uq", "kv_a_proj": "w_dkv", "g_proj": "w_gate",
    "q_a_norm": "q_norm", "kv_a_norm": "kv_norm", "kv_b_proj": "w_ukv"}
DENSE_NAMES = {"gate_proj": "w_gate", "up_proj": "w_up",
               "down_proj": "w_down"}


def reference_weights(params, model_config):
    """The same arrays under ``reference/gigachat3_5.py``'s names."""
    p = params["params"]
    layers = []
    for i in range(model_config["num_hidden_layers"]):
        m = p[f"layers_{i}"]
        mixer = {MIXER_NAMES.get(k, k): v["kernel"] if isinstance(v, dict)
                 else v for k, v in m["mixer"].items()}
        ffn = {DENSE_NAMES.get(k, k): v for k, v in m["ffn"].items()}
        layers.append({"mixer": mixer, "ffn": ffn,
                       **{k: m[k] for k in ("mixer_pre", "mixer_post",
                                            "ffn_pre", "ffn_post")}})
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
