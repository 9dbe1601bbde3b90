"""Kimi-K2.6's cut as a ``CausalLM`` stage: weights and prompts from the
seed.

Weights are ``builders/gigachat3_5.py``'s scheme (made on the device,
one key a tensor from the seed and the tensor's name, every tensor in
the dtype the configuration states): matrices normal at 0.02, the
embedding at 1.0, the router normal at 0.5 over the root of the hidden
size (scores of about 0.35-0.7, so the chosen eight fall anywhere in the
384) with a selection bias normal at 0.02. A norm's weight here is a
plain scale, so it is that scheme's vector (normal at 0.2 about zero)
plus one: scales of about 0.8-1.2, off 1, so that a norm left out or
taken for the gated kind shows. ``reference_weights`` is the same
arrays under the plain reference's names.
"""

from benchmark.lookup import load_module

_gigachat = load_module("builders", "gigachat3_5")
make_frames = _gigachat.make_frames

NORMS = ("mixer_pre", "ffn_pre", "q_a_norm", "kv_a_norm", "final_norm")


def make_weights(seed, model_config):
    """The stage's parameter pytree (``backbones.lm_param_shapes``)."""
    import jax

    def place(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        return leaf + 1 if name in NORMS else leaf

    return jax.tree_util.tree_map_with_path(
        place, _gigachat.make_weights(seed, model_config))


MIXER_NAMES = {"q_a_proj": "w_dq", "q_b_proj": "w_uq", "kv_a_proj": "w_dkv",
               "o_proj": "wo", "q_a_norm": "q_norm", "kv_a_norm": "kv_norm",
               "kv_b_proj": "w_ukv"}
DENSE_NAMES = {"gate_proj": "w_gate", "up_proj": "w_up",
               "down_proj": "w_down"}


def reference_weights(params, model_config):
    """The same arrays under ``reference/kimi_k2.py``'s names."""
    p = params["params"]
    layers = []
    for i in range(model_config["num_hidden_layers"]):
        m = p[f"layers_{i}"]
        mixer = {MIXER_NAMES[k]: v["kernel"] if isinstance(v, dict) else v
                 for k, v in m["mixer"].items()}
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
