"""Xing4.0-29B-A4B's cut as a ``CausalLM`` stage: weights and prompts
from the seed.

Weights are ``builders/kimi_k2.py``'s (made on the device, one key a
tensor from the seed and the tensor's name, every tensor in the dtype
the configuration states; matrices normal at 0.02, the embedding at
1.0, norm scales about 0.8-1.2, the router normal at 0.5 over the root
of the hidden size). The leaves of the residual path (``*_hc``) are
that scheme's draws moved off the trivial, so that no fault in the path
can hide behind a coefficient that is constant, an identity or
uniform:

- ``phi`` normal at ``(n C) ** -0.5``: the normed stream's projections
  ``p``, ``q`` and ``r`` are of order 1 and differ from token to token;
- ``alpha`` of order 1 (0.4-0.8), so the coefficients move with them;
- ``b_pre`` and ``b_post`` normal at 0.2: ``H_pre`` about 0.2-0.8,
  ``H_post`` about 0.5-1.5;
- ``b_res`` 1 on the diagonal, normal at 0.5 elsewhere and on it:
  ``H_res`` leans to the identity (what a trained residual path keeps)
  with entries of about 0.05-0.7, neither the identity nor uniform.

``reference_weights`` is the same arrays under the plain reference's
names.
"""

from benchmark.lookup import load_module

_kimi = load_module("builders", "kimi_k2")
make_frames = _kimi.make_frames

HC = ("mixer_hc", "ffn_hc")


def make_weights(seed, model_config):
    """The stage's parameter pytree (``backbones.lm_param_shapes``)."""
    import jax
    import jax.numpy as jnp

    def place(path, leaf):
        keys = [str(getattr(p, "key", p)) for p in path]
        if len(keys) < 2 or keys[-2] not in HC:
            return leaf
        draw = leaf.astype(jnp.float32)
        if keys[-1] == "phi":            # drawn at 0.02
            moved = draw * (50.0 * leaf.shape[0] ** -0.5)
        elif keys[-1] == "alpha":        # vectors are drawn at 0.2
            moved = 0.6 + draw
        elif keys[-1] == "b_res":        # a matrix: drawn at 0.02
            moved = 25.0 * draw + jnp.eye(leaf.shape[0])
        else:
            moved = draw
        return moved.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(
        place, _kimi.make_weights(seed, model_config))


def reference_weights(params, model_config):
    """The same arrays under ``reference/xing4_0.py``'s names."""
    weights = _kimi.reference_weights(params, model_config)
    for i, layer in enumerate(weights["layers"]):
        layer.update({k: params["params"][f"layers_{i}"][k] for k in HC})
    return weights


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
