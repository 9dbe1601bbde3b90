"""Flax backbone zoo for the deep-learning estimators.

The reference fine-tunes torchvision/HF checkpoints pulled from the
network (dl/DeepVisionClassifier.py backbone param). This environment is
zero-egress, so the zoo is built in-repo: a compact ResNet family and a
transformer encoder, both TPU-shaped (NHWC convs, bf16-friendly widths,
optional ring attention for long sequences), and one causal decoder
that carries a state from one forward pass to the next,
:class:`HybridLM`, whose layers the config chooses.

A decoder's config is the model's own ``config.json`` as a dict;
``model_type`` names its family (``FAMILY_DEFAULTS``; a config without
the key is ``brumby``'s), and a family reads these keys:

- ``brumby``: every layer a :class:`DecoderBlock` (power retention over
  grouped heads, then SwiGLU): ``hidden_size``, ``num_attention_heads``,
  ``num_key_value_heads``, ``head_dim``, ``intermediate_size``,
  ``vocab_size``, ``num_hidden_layers``, ``rms_norm_eps``,
  ``rope_theta``, ``torch_dtype``.
- ``gigachat3_5`` and ``kimi_k2``: :class:`HybridBlock` layers, latent
  attention where ``full_attention_layers`` lists the layer (every
  layer where the key is absent), else the gated delta rule (its
  ``linear_*`` keys), over a dense SwiGLU under
  ``first_k_dense_replace``, else sparse experts: ``hidden_size``,
  ``num_hidden_layers``, ``intermediate_size``,
  ``moe_intermediate_size``, ``n_routed_experts``,
  ``n_shared_experts``, ``num_experts_per_tok``,
  ``routed_scaling_factor``, ``q_lora_rank``, ``kv_lora_rank``,
  ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
  ``num_attention_heads``, ``rope_theta``, ``rope_scaling``,
  ``rms_norm_eps``, ``vocab_size``, ``torch_dtype``; where the config
  states them, ``norm_type``, ``layernorm_type``, ``gated_attention``,
  ``swiglu_limit``, ``hidden_clamp``; and ``experts_held`` and
  ``router_experts`` where this chip holds a share of the experts.
- ``xing4_0``: ``kimi_k2``'s keys and the residual path's
  (``parallel/hyper.py``): ``hc_mult`` streams a token,
  ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min``,
  ``mhc_h_res_clamp_max``.
- ``Motif``: its own ``config.json``'s keys, read through
  ``FAMILY_KEYS``: ``attention_cls`` ``gdla`` (grouped differential
  attention on the latent cache), ``num_noise_heads``,
  ``sliding_window`` and its pattern (window layers on a ring),
  ``hidden_act`` ``poly_norm``, ``mhc_*``; ``published_layers`` where a
  cut keeps some of the published layers.
- ``mimo_v2``: its own ``config.json``'s keys, read through
  ``FAMILY_KEYS``: every layer grouped-query attention over a plain
  key/value cache (``parallel/kv.py``), full or over a window of
  ``sliding_window`` by ``hybrid_layer_pattern`` (1: window), each kind
  with its own key heads and rotary base (``swa_*``), the first
  ``partial_rotary_factor`` of a head's dims rotated, a sink logit a
  head where ``add_swa_attention_sink_bias`` (or ``_full_``) says so,
  the values scaled by ``attention_value_scale``; a dense SwiGLU or
  sparse experts by ``moe_layer_freq``; ``published_layers`` where a
  cut keeps some of the published layers.
"""

from __future__ import annotations

import functools
from typing import Any, Mapping, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


class ResNetBlock(nn.Module):
    features: int
    strides: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(self.features, (3, 3), strides=(self.strides,) * 2,
                    use_bias=False)(x)
        y = nn.GroupNorm(num_groups=min(8, self.features))(y)
        y = nn.relu(y)
        y = nn.Conv(self.features, (3, 3), use_bias=False)(y)
        y = nn.GroupNorm(num_groups=min(8, self.features))(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.features, (1, 1),
                               strides=(self.strides,) * 2,
                               use_bias=False)(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    """Small ResNet over NHWC images."""

    num_classes: int
    stage_sizes: Sequence[int] = (2, 2, 2)
    width: int = 32

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(self.width, (3, 3), use_bias=False)(x)
        x = nn.GroupNorm(num_groups=8)(x)
        x = nn.relu(x)
        for i, n_blocks in enumerate(self.stage_sizes):
            feats = self.width * (2 ** i)
            for b in range(n_blocks):
                x = ResNetBlock(feats, strides=2 if b == 0 and i > 0 else 1)(x)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        return nn.Dense(self.num_classes)(x)


class SimpleCNN(nn.Module):
    num_classes: int
    width: int = 16

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(self.width, (3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(self.width * 2, (3, 3))(x)
        x = nn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes)(x)


VISION_BACKBONES = {
    "resnet18": lambda n: ResNet(num_classes=n, stage_sizes=(2, 2, 2, 2),
                                 width=64),
    "resnet_small": lambda n: ResNet(num_classes=n),
    "simple_cnn": lambda n: SimpleCNN(num_classes=n),
}


class TransformerBlock(nn.Module):
    dim: int
    heads: int

    @nn.compact
    def __call__(self, x, mask=None):
        y = nn.LayerNorm()(x)
        y = nn.MultiHeadDotProductAttention(
            num_heads=self.heads, qkv_features=self.dim,
            deterministic=True)(y, mask=mask)
        x = x + y
        y = nn.LayerNorm()(x)
        y = nn.Dense(self.dim * 4)(y)
        y = nn.gelu(y)
        y = nn.Dense(self.dim)(y)
        return x + y


class TextTransformer(nn.Module):
    """Token-id transformer encoder with mean pooling + classifier."""

    num_classes: int
    vocab_size: int = 1 << 15
    dim: int = 64
    heads: int = 4
    layers: int = 2
    max_len: int = 128
    pool: str = "mean"  # mean | cls

    @nn.compact
    def __call__(self, token_ids):
        # token_ids: (b, n) int32; 0 is padding
        pad_mask = (token_ids > 0)
        pos = jnp.arange(token_ids.shape[1])
        x = nn.Embed(self.vocab_size, self.dim)(token_ids)
        x = x + nn.Embed(self.max_len, self.dim)(pos)[None, :, :]
        attn_mask = nn.make_attention_mask(pad_mask, pad_mask)
        for _ in range(self.layers):
            x = TransformerBlock(self.dim, self.heads)(x, mask=attn_mask)
        x = nn.LayerNorm()(x)
        denom = jnp.maximum(pad_mask.sum(axis=1, keepdims=True), 1)
        pooled = (x * pad_mask[:, :, None]).sum(axis=1) / denom
        if self.num_classes == 0:  # embedding mode
            return pooled
        return nn.Dense(self.num_classes)(pooled)


# ---------------------------------------------------------------------
# causal decoder with recurrent state (dl/causal_lm.py serves it)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def lm_dtype(config: Mapping[str, Any]):
    """The dtype a language-model config states (``torch_dtype``, the
    key of the model's own ``config.json``); float32 where it is silent."""
    return _DTYPES[str(config.get("torch_dtype", "float32"))]


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rotary(x, positions, theta):
    """``x``: ``(B, T, heads, d)``; ``positions``: ``(B, T)``. Element
    ``j`` turns with ``j + d / 2`` (the "rotate_half" convention)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(angle)[:, :, None, :], jnp.sin(angle)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


class Linear(nn.Module):
    """Bias-free ``x @ kernel``: operands in the model's dtype (through
    ``placement_cast``, the one low-precision seam), float32 out."""

    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        from mmlspark_tpu.parallel.shard_rules import placement_cast

        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (x.shape[-1], self.features), self.dtype)
        return jnp.matmul(placement_cast(x, self.dtype),
                          placement_cast(kernel, self.dtype),
                          preferred_element_type=jnp.float32)


def _scale(module, name, width):
    return module.param(name, nn.initializers.ones, (width,), jnp.float32)


class DecoderBlock(nn.Module):
    """One ``brumby`` layer: pre-norm power retention over grouped heads
    (``parallel/retention.py``), then pre-norm SwiGLU. ``config`` holds
    the keys of the model's ``config.json``; ``index`` is the layer's
    (every layer of the family is alike). Its leaves keep the flat
    names of the family's checkpoint (``attn_norm``, ``q_proj/kernel``,
    ``gate_proj/kernel``, ...)."""

    config: Any
    index: int = 0

    def sparse(self) -> bool:
        return False

    def init_state(self, batch, capacity):
        """The layer's retention state, zero; no cache (``capacity``
        means nothing to a state that does not grow)."""
        from mmlspark_tpu.parallel import retention

        c = self.config
        return retention.init_state(batch, c["num_key_value_heads"],
                                    c["head_dim"])

    @nn.compact
    def __call__(self, h, positions, lengths, state):
        from mmlspark_tpu.parallel import retention

        c = self.config
        heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
        d, eps, dtype = c["head_dim"], c["rms_norm_eps"], lm_dtype(c)
        b, t, _ = h.shape

        with jax.named_scope("lm.retention"):
            x = rms_norm(h, _scale(self, "attn_norm", h.shape[-1]), eps)
            q = Linear(heads * d, dtype, name="q_proj")(x)
            k = Linear(kv * d, dtype, name="k_proj")(x)
            v = Linear(kv * d, dtype, name="v_proj")(x)
            q = rotary(rms_norm(q.reshape(b, t, heads, d),
                                _scale(self, "q_norm", d), eps),
                       positions, c["rope_theta"])
            k = rotary(rms_norm(k.reshape(b, t, kv, d),
                                _scale(self, "k_norm", d), eps),
                       positions, c["rope_theta"])
            v = v.reshape(b, t, kv, d)
            log_g = jax.nn.log_sigmoid(
                Linear(kv, dtype, name="g_proj")(x)
                + self.param("g_bias", nn.initializers.constant(4.0),
                             (kv,), jnp.float32))
            q, k, v = (_rounded(a, dtype) for a in (q, k, v))
            scale = c.get("retention_scale", d ** -0.5)
            ret_eps = c.get("retention_eps", retention.EPS)
            if t == 1:
                real = (lengths > 0)[:, None]      # padding: g = 1, k = 0
                y, state = retention.retention_step(
                    q[:, 0], jnp.where(real[..., None], k[:, 0], 0.0),
                    v[:, 0], jnp.where(real, log_g[:, 0], 0.0), state,
                    scale=scale, eps=ret_eps)
                y = y[:, None]
            else:
                y, state = retention.retention_prefill(
                    q, k, v, log_g, lengths, state, scale=scale,
                    chunk=t, eps=ret_eps)
            h = h + Linear(h.shape[-1], dtype, name="o_proj")(
                y.reshape(b, t, heads * d))
        with jax.named_scope("lm.mlp"):
            x = rms_norm(h, _scale(self, "mlp_norm", h.shape[-1]), eps)
            gate = Linear(c["intermediate_size"], dtype, name="gate_proj")(x)
            up = Linear(c["intermediate_size"], dtype, name="up_proj")(x)
            h = h + Linear(h.shape[-1], dtype, name="down_proj")(
                nn.silu(gate) * up)
        return h, state, None


def lm_module(config: Mapping[str, Any]):
    """The language model of a config's family (``model_type``, one of
    ``FAMILY_DEFAULTS``; a config without the key is ``brumby``'s),
    given the config with the keys its code reads (``family_config``)."""
    kind = config.get("model_type", "brumby")
    if kind not in FAMILY_DEFAULTS:
        raise ValueError(f"model_type {kind!r} is not one of "
                         f"{sorted(FAMILY_DEFAULTS)}")
    return HybridLM(family_config(config))


def lm_init_state(config: Mapping[str, Any], batch: int, capacity: int = 0):
    """An empty state for ``batch`` sequences: no token absorbed, every
    recurrent state zero (float32, whatever the model's dtype), every
    cache empty with room for ``capacity`` positions (a window layer's
    ring: its window's, whatever ``capacity``)."""
    module = lm_module(config)
    return module.init_state(module.config, batch, capacity)


def lm_state_bytes(config: Mapping[str, Any], batch: int,
                   capacity: int = 0) -> Mapping[str, int]:
    """Bytes of ``lm_init_state(config, batch, capacity)``, by kind:
    ``{"state": the fixed recurrent part, "cache": the part that grows
    with ``capacity``}``."""
    state = jax.eval_shape(
        lambda: lm_init_state(config, batch, capacity))
    empty = jax.eval_shape(lambda: lm_init_state(config, batch, 0))

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))

    return {"state": nbytes(empty), "cache": nbytes(state) - nbytes(empty)}


def lm_init_params(config: Mapping[str, Any], seed: int = 0):
    """Freshly initialised parameters of the config's model."""
    return lm_module(config).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 2), jnp.int32),
        jnp.full((1,), 2, jnp.int32), lm_init_state(config, 1, 2))


def lm_param_shapes(config: Mapping[str, Any]):
    """The same pytree as shapes and dtypes, without materialising it."""
    return jax.eval_shape(lambda: lm_init_params(config))


# ---------------------------------------------------------------------
# the hybrid layer: delta-rule and latent-attention mixers over a dense
# SwiGLU or sparse experts, the residual one vector a token or, with
# ``hc_mult``, that many streams (``parallel/hyper.py``)


def gated_norm(x, weight, eps):
    """RMSNorm whose scale is ``2 sigmoid(weight)``: ``weight`` is
    stored centred at zero, where the scale is 1
    (``ZeroCenteredGatedNorm``, ``layernorm_gating_weight`` 2)."""
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * 2.0 * jax.nn.sigmoid(weight.astype(jnp.float32)))


def _centred(module, name, width):
    return module.param(name, nn.initializers.zeros, (width,), jnp.float32)


# the families, and what a family's code does where its config.json
# may be silent; a config without these keys has plain RMSNorm before
# each sub-layer and none after, a selection bias on the router and,
# with a residual of several streams, xing4_0's epsilon and clamp.
# ``block`` ``retention``: every layer a DecoderBlock (else a
# HybridBlock), and ``attention`` ``kv``: every layer's mixer a KVMixer,
# each read from this table alone and never from a config
FAMILY_DEFAULTS = {"brumby": {"block": "retention"},
                   "gigachat3_5": {"norm_type": "ZeroCenteredGatedNorm",
                                   "layernorm_type": "pre_post"},
                   "kimi_k2": {}, "xing4_0": {},
                   "Motif": {"hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
                             "mhc_h_res_clamp_max": 30,
                             "selection_bias": False},
                   "mimo_v2": {"attention": "kv"}}


def _motif_keys(c):
    return {"n_routed_experts": c["num_experts"],
            "num_experts_per_tok": c["experts_top_k"],
            "routed_scaling_factor": c["route_scale"],
            "first_k_dense_replace": c["n_dense_first_layers"],
            "n_shared_experts": c["num_shared_experts"],
            "qk_nope_head_dim": c["head_dim"] - c["qk_rope_head_dim"],
            "gated_attention": c["elementwise_attn_output_gate"],
            "hc_mult": c["mhc_expansion_rate"],
            "hc_sinkhorn_iters": c["mhc_sinkhorn_iters"]}


def _mimo_keys(c):
    # routed_scaling_factor null: the chosen weights as normalised;
    # n_shared_experts null: no shared expert
    return {"rms_norm_eps": c["layernorm_epsilon"],
            "routed_scaling_factor": 1.0, "n_shared_experts": 0}


# the keys this module's code reads, from a family's config.json where
# it names them otherwise (Motif: no qk_nope_head_dim, head_dim the
# query's and key's whole width)
FAMILY_KEYS = {"Motif": _motif_keys, "mimo_v2": _mimo_keys}


def family_config(config: Mapping[str, Any]) -> dict:
    """``config`` with the keys ``FAMILY_KEYS`` derives for its family
    where it lacks them or holds null; another family's config as it
    is."""
    c = dict(config)
    derive = FAMILY_KEYS.get(c.get("model_type"))
    for key, value in (derive(c) if derive else {}).items():
        if c.get(key) is None:
            c[key] = value
    return c


def _setting(config, key):
    return config.get(key, FAMILY_DEFAULTS.get(
        config.get("model_type", "brumby"), {}).get(key))


def _gated(config) -> bool:
    return _setting(config, "norm_type") == "ZeroCenteredGatedNorm"


def norm_weight(module, name, width, config):
    """The weight of one of the model's norms: centred at zero for the
    gated norm, a scale about one for plain RMSNorm."""
    return (_centred if _gated(config) else _scale)(module, name, width)


def model_norm(x, weight, config):
    """The model's norm over ``x``'s last axis: the gated norm where
    the config names it (``norm_type`` ``ZeroCenteredGatedNorm``), else
    plain RMSNorm."""
    return (gated_norm if _gated(config) else rms_norm)(
        x, weight, config["rms_norm_eps"])


def block_norm(module, name, x, config):
    """``model_norm`` with the weight ``name`` declared on ``module``."""
    return model_norm(x, norm_weight(module, name, x.shape[-1], config),
                      config)


def _rounded(x, dtype):
    """``x`` at the model's precision, kept float32 for the state's
    arithmetic: ``reduce_precision`` is the rounding that XLA may not
    drop (a float32 -> bfloat16 -> float32 pair of converts it may, as
    excess precision)."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def experts_held(config: Mapping[str, Any]):
    """``(first, count, router width)``: the run of the published
    experts this chip holds (``experts_held``; all ``n_routed_experts``
    where the key is absent) and how many the router scores
    (``router_experts``)."""
    count = config["n_routed_experts"]
    first, stop = config.get("experts_held", (0, count))
    return first, stop - first, config.get("router_experts", count)


class DeltaMixer(nn.Module):
    """Gated delta rule over grouped heads (``parallel/delta_rule.py``):
    a causal depth-wise convolution and SiLU on q, k and v, L2-normed q
    and k, a decay and a write strength a value head, and a gated norm
    on the output. State: ``{"s": (B, value heads, d_k, d_v)`` float32,
    ``"conv": (B, width - 1, channels)`` the last tokens' channels
    before the convolution``}``."""

    config: Any

    @staticmethod
    def init_state(config, batch, capacity=0):
        from mmlspark_tpu.parallel import delta_rule

        c = config
        kh, vh = c["linear_num_key_heads"], c["linear_num_value_heads"]
        d_k, d_v = c["linear_key_head_dim"], c["linear_value_head_dim"]
        return {"s": delta_rule.init_state(batch, vh, d_k, d_v),
                "conv": jnp.zeros((batch, c["linear_conv_kernel_dim"] - 1,
                                   2 * kh * d_k + vh * d_v), jnp.float32)}

    @nn.compact
    def __call__(self, x, positions, lengths, state):
        from mmlspark_tpu.parallel import delta_rule

        c = self.config
        kh, vh = c["linear_num_key_heads"], c["linear_num_value_heads"]
        d_k, d_v = c["linear_key_head_dim"], c["linear_value_head_dim"]
        width, dtype = c["linear_conv_kernel_dim"], lm_dtype(c)
        b, t, _ = x.shape
        mixed = jnp.concatenate(
            [Linear(kh * d_k, dtype, name="q_proj")(x),
             Linear(kh * d_k, dtype, name="k_proj")(x),
             Linear(vh * d_v, dtype, name="v_proj")(x)], axis=-1)
        z = Linear(vh * d_v, dtype, name="z_proj")(x)
        beta = jax.nn.sigmoid(Linear(vh, dtype, name="b_proj")(x))
        log_g = -jnp.exp(self.param(
            "A_log", nn.initializers.zeros, (vh,), jnp.float32).astype(
                jnp.float32)) * jax.nn.softplus(
            Linear(vh, dtype, name="a_proj")(x) + self.param(
                "dt_bias", nn.initializers.zeros, (vh,), jnp.float32))
        taps = self.param("conv", nn.initializers.normal(0.5),
                          (width, mixed.shape[-1]), jnp.float32)
        # the convolution reaches width - 1 tokens back: the state's tail
        # before the stretch; the new tail is the last real tokens' rows
        seen = jnp.concatenate([state["conv"], mixed], axis=1)
        tail = jax.vmap(lambda rows, at: jax.lax.dynamic_slice_in_dim(
            rows, at, width - 1, axis=0))(seen, lengths)
        mixed = nn.silu(sum(
            taps[j].astype(jnp.float32) * seen[:, j:j + t]
            for j in range(width)))
        q, k, v = jnp.split(mixed, [kh * d_k, 2 * kh * d_k], axis=-1)

        def unit(a):
            a = a.reshape(b, t, kh, d_k)
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        q = _rounded(unit(q) * d_k ** -0.5, dtype)
        k = _rounded(unit(k), dtype)
        v = _rounded(v.reshape(b, t, vh, d_v), dtype)
        if t == 1:
            real = (lengths > 0)[:, None]          # padding: g = 1, beta = 0
            o, s = delta_rule.delta_step(
                q[:, 0], k[:, 0], v[:, 0], jnp.where(real, log_g[:, 0], 0.0),
                jnp.where(real, beta[:, 0], 0.0), state["s"])
            o = o[:, None]
        else:
            o, s = delta_rule.delta_prefill(q, k, v, log_g, beta, lengths,
                                            state["s"])
        o = gated_norm(o, _centred(self, "o_norm", d_v),
                       c["linear_attn_o_norm_eps"])
        o = o * 2.0 * jax.nn.sigmoid(z.reshape(b, t, vh, d_v))
        return (Linear(x.shape[-1], dtype, name="o_proj")(
            o.reshape(b, t, vh * d_v)), {"s": s, "conv": tail})


def latent_inputs(module, x, positions, key_heads):
    """What a latent mixer makes of its input, its leaves declared on
    ``module``: ``(q_n, q_r, c_kv, k_r, w_uk, w_uv, softmax scale)``,
    the ``num_attention_heads`` queries (their rotated part turned),
    the normed latent and the rotated key a position caches, and the up
    projection to ``key_heads`` keys and values."""
    from mmlspark_tpu.parallel import latent

    c = module.config
    heads, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    d_v, dtype = c["v_head_dim"], lm_dtype(c)
    b, t, _ = x.shape
    scaling = c.get("rope_scaling") or {}
    freq = latent.yarn_frequencies(rope, c["rope_theta"], scaling)
    scale = latent.softmax_scale(
        nope + rope, scaling, c.get("use_mla_scaling_factor",
                                    bool(scaling.get("mscale_all_dim"))))

    c_q = block_norm(module, "q_a_norm", Linear(
        c["q_lora_rank"], dtype, name="q_a_proj")(x), c)
    q = Linear(heads * (nope + rope), dtype, name="q_b_proj")(c_q)
    q = q.reshape(b, t, heads, nope + rope)
    q_n = q[..., :nope]
    q_r = latent.rotary_interleaved(q[..., nope:], positions, freq)
    down = Linear(rank + rope, dtype, name="kv_a_proj")(x)
    c_kv = block_norm(module, "kv_a_norm", down[..., :rank], c)
    k_r = latent.rotary_interleaved(down[..., rank:], positions, freq)
    up = module.param("kv_b_proj", nn.initializers.normal(0.02),
                      (rank, key_heads, nope + d_v), dtype)
    return q_n, q_r, c_kv, k_r, up[..., :nope], up[..., nope:], scale


class LatentMixer(nn.Module):
    """Latent attention (``parallel/latent.py``): low-rank queries,
    keys and values compressed to one latent and one rotated key a
    position, and where the config asks (``gated_attention``) a sigmoid
    gate a head channel on the output. Under YaRN the softmax scale
    carries ``mscale_all_dim`` where ``use_mla_scaling_factor`` says
    so, and where the key is absent whenever ``mscale_all_dim`` is set
    (the DeepSeek-V3 family's code). State: the cache ``{"c", "r"}`` in
    the model's dtype. A prefill's attention (``latent.latent_prefill``)
    stands under ``lm.mla.full``, as a full layer's of
    :class:`DiffLatentMixer`."""

    config: Any

    @staticmethod
    def init_state(config, batch, capacity=0):
        from mmlspark_tpu.parallel import latent

        return latent.init_cache(batch, capacity, config["kv_lora_rank"],
                                 config["qk_rope_head_dim"],
                                 lm_dtype(config))

    @nn.compact
    def __call__(self, x, positions, lengths, state):
        from mmlspark_tpu.parallel import latent

        c = self.config
        heads, d_v, dtype = c["num_attention_heads"], c["v_head_dim"], \
            lm_dtype(c)
        b, t, _ = x.shape
        q_n, q_r, c_kv, k_r, w_uk, w_uv, scale = latent_inputs(
            self, x, positions, heads)
        pos = positions[:, 0]
        with jax.named_scope("lm.mla.write"):
            cache = latent.write_entries(state, {"c": c_kv, "r": k_r},
                                         pos, lengths)
        if t == 1:
            with jax.named_scope("lm.mla.decode"):
                o = latent.latent_decode(
                    q_n[:, 0], q_r[:, 0], cache, w_uk, w_uv, pos,
                    scale=scale, dtype=dtype)[:, None]
        else:
            with jax.named_scope("lm.mla.full"):
                o = latent.latent_prefill(q_n, q_r, cache, w_uk, w_uv, pos,
                                          lengths, scale=scale, dtype=dtype)
        o = o.reshape(b, t, heads * d_v)
        if c.get("gated_attention", False):
            o = o * jax.nn.sigmoid(Linear(heads * d_v, dtype,
                                          name="g_proj")(x))
        return Linear(x.shape[-1], dtype, name="o_proj")(o), cache


class DiffLatentMixer(nn.Module):
    """Grouped differential attention on the latent cache (GDLA,
    ``attention_cls`` ``gdla``): :class:`LatentMixer`'s queries and
    cache, the ``num_attention_heads`` query heads grouped over
    ``num_key_value_heads`` key and value heads expanded from the
    latent (``W_uk``, ``W_uv``: ``(rank, key heads, .)``). Of a group's
    consecutive query heads the first is its noise head
    (``num_noise_heads``, one a group), the others its signal heads;
    a signal head's output is its own attention map less
    ``lambda = sigmoid(x W_lambda)`` (a token and signal head:
    ``diff_v2``) times its group's noise map, applied to the group's
    values, then the sigmoid gate a head channel and ``W_o``. The maps
    are linear in the output, so each head attends as it would alone
    and the difference is taken after (``lm.mla.diff``).

    With ``window`` the layer sees the ``sliding_window`` most recent
    positions alone, its own included, and its state is a ring of that
    many slots (``{"ring": {"c", "r"}}``, position ``p`` in slot ``p mod
    window``; ``latent.ring_entries``, ``latent.window_prefill``); a
    decode step reads the ring with the cache's decode, through which
    the slots' order does not matter. The core and the ring's write
    stand under ``lm.mla.window``; a full layer's prefill attention
    (``latent.latent_prefill``) under ``lm.mla.full``."""

    config: Any
    window: bool = False

    @staticmethod
    def ring(config, batch):
        """A window layer's empty state; a full layer's is
        :class:`LatentMixer`'s cache."""
        from mmlspark_tpu.parallel import latent

        return {"ring": latent.init_cache(
            batch, config["sliding_window"], config["kv_lora_rank"],
            config["qk_rope_head_dim"], lm_dtype(config))}

    @nn.compact
    def __call__(self, x, positions, lengths, state):
        from mmlspark_tpu.parallel import latent

        c = self.config
        heads, groups = c["num_attention_heads"], c["num_key_value_heads"]
        signal = heads - c["num_noise_heads"]
        d_v, dtype = c["v_head_dim"], lm_dtype(c)
        b, t, _ = x.shape
        q_n, q_r, c_kv, k_r, w_uk, w_uv, scale = latent_inputs(
            self, x, positions, groups)
        pos = positions[:, 0]

        def decode(cache, filled):
            # the absorbed decode a query head: its group's W_uk, W_uv
            def each(w):
                return jnp.repeat(w, heads // groups, axis=1)
            return latent.latent_decode(
                q_n[:, 0], q_r[:, 0], cache, each(w_uk), each(w_uv), filled,
                scale=scale, dtype=dtype)[:, None]

        if self.window:
            with jax.named_scope("lm.mla.window"):
                ring = state["ring"]
                width = ring["c"].shape[1]
                if t == 1:
                    ring = latent.ring_entries(
                        ring, {"c": c_kv, "r": k_r}, pos, lengths)
                    o = decode(ring, jnp.minimum(pos, width - 1))
                else:
                    o = latent.window_prefill(
                        q_n, q_r, ring, c_kv, k_r, w_uk, w_uv, pos, lengths,
                        scale=scale, dtype=dtype)
                    ring = latent.ring_entries(
                        ring, {"c": c_kv, "r": k_r}, pos, lengths)
                state = {"ring": ring}
        else:
            with jax.named_scope("lm.mla.write"):
                state = latent.write_entries(state, {"c": c_kv, "r": k_r},
                                             pos, lengths)
            if t == 1:
                with jax.named_scope("lm.mla.decode"):
                    o = decode(state, pos)
            else:
                with jax.named_scope("lm.mla.full"):
                    o = latent.latent_prefill(q_n, q_r, state, w_uk, w_uv,
                                              pos, lengths, scale=scale,
                                              dtype=dtype)
        with jax.named_scope("lm.mla.diff"):
            o = o.reshape(b, t, groups, heads // groups, d_v)
            lam = jax.nn.sigmoid(Linear(signal, dtype, name="lambda_proj")(
                x)).reshape(b, t, groups, -1, 1)
            o = (o[..., 1:, :] - lam * o[..., :1, :]).reshape(
                b, t, signal * d_v)
            if c.get("gated_attention", False):
                o = o * jax.nn.sigmoid(Linear(signal * d_v, dtype,
                                              name="g_proj")(x))
        return Linear(x.shape[-1], dtype, name="o_proj")(o), state


def kv_widths(config, window: bool):
    """``(query heads, key heads, head_dim, rotated dims, v_head_dim,
    rotary base)`` of a :class:`KVMixer`: a window layer's from the
    ``swa_`` keys where the config has them."""
    def get(key):
        return config.get("swa_" + key, config[key]) if window \
            else config[key]

    d = get("head_dim")
    return (get("num_attention_heads"), get("num_key_value_heads"), d,
            int(config["partial_rotary_factor"] * d), get("v_head_dim"),
            get("rope_theta"))


class KVMixer(nn.Module):
    """Grouped-query attention over a plain key/value cache
    (``parallel/kv.py``): one fused projection to the queries, keys and
    values, the first ``partial_rotary_factor`` of each query and key
    head's dims rotated ("rotate_half" over them, the rest passed as
    they are), ``heads / key heads`` consecutive query heads a key head,
    the softmax scaled by ``head_dim ** -0.5``, a learned sink logit a
    head in the denominator where the config adds one to the layer's
    kind, the values scaled by ``attention_value_scale``, then
    ``W_o``. Each kind has its own widths (``kv_widths``).

    State: a full layer's cache ``{"k_r", "k_n", "v"}`` of ``capacity``
    positions in the model's dtype; with ``window``, ``{"ring": ...}``
    the same of ``sliding_window`` slots, the layer seeing the
    ``sliding_window`` most recent positions alone, its own included
    (``latent.ring_entries``, ``kv.window_prefill``; a decode step reads
    the ring with the cache's decode). The whole mixer stands under
    ``lm.kv.full`` or ``lm.kv.window``."""

    config: Any
    window: bool = False

    @staticmethod
    def init_state(config, batch, capacity=0, window=False):
        from mmlspark_tpu.parallel import kv

        _, key_heads, d, rope, d_v, _ = kv_widths(config, window)
        cache = kv.init_cache(batch, config["sliding_window"] if window
                              else capacity, key_heads, rope, d - rope, d_v,
                              lm_dtype(config))
        return {"ring": cache} if window else cache

    @nn.compact
    def __call__(self, x, positions, lengths, state):
        from mmlspark_tpu.parallel import kv, latent

        c = self.config
        heads, key_heads, d, rope, d_v, theta = kv_widths(c, self.window)
        dtype, (b, t, _) = lm_dtype(c), x.shape
        kind = "swa" if self.window else "full"
        with jax.named_scope(f"lm.kv.{'window' if self.window else 'full'}"):
            qkv = Linear(heads * d + key_heads * (d + d_v), dtype,
                         name="qkv_proj")(x)
            q, k, v = jnp.split(qkv, [heads * d, (heads + key_heads) * d],
                                axis=-1)

            def turned(a, n):
                a = a.reshape(b, t, n, d)
                return jnp.concatenate([rotary(a[..., :rope], positions,
                                               theta), a[..., rope:]],
                                       axis=-1)

            q, k = turned(q, heads), turned(k, key_heads)
            new = kv.entries(k, v.reshape(b, t, key_heads, d_v), rope)
            sink = (self.param("sink", nn.initializers.zeros, (heads,),
                               jnp.float32)
                    if c.get(f"add_{kind}_attention_sink_bias") else None)
            pos, scale = positions[:, 0], d ** -0.5
            if self.window:
                ring = state["ring"]
                if t == 1:
                    ring = latent.ring_entries(ring, new, pos, lengths)
                    last = jnp.minimum(pos, ring["v"].shape[1] - 1)
                    o = kv.kv_decode(q[:, 0], ring, last, scale=scale,
                                     dtype=dtype, sink=sink)[:, None]
                else:
                    o = kv.window_prefill(q, ring, new, pos, lengths,
                                          scale=scale, dtype=dtype, sink=sink)
                    ring = latent.ring_entries(ring, new, pos, lengths)
                state = {"ring": ring}
            else:
                state = latent.write_entries(state, new, pos, lengths)
                if t == 1:
                    o = kv.kv_decode(q[:, 0], state, pos, scale=scale,
                                     dtype=dtype, sink=sink)[:, None]
                else:
                    o = kv.kv_prefill(q, state, pos, lengths, scale=scale,
                                      dtype=dtype)
            o = o.reshape(b, t, heads * d_v) * c.get("attention_value_scale",
                                                     1.0)
            return Linear(x.shape[-1], dtype, name="o_proj")(o), state


def _poly_norm(module, config, prefix="", stack=()):
    """The :class:`~mmlspark_tpu.parallel.experts.PolyNorm` of one MLP
    (or of ``stack`` experts) where the config's ``hidden_act`` is
    ``poly_norm``, its leaves declared on ``module``; else ``None``
    (SiLU). The weights start at a third each, the bias at zero."""
    from mmlspark_tpu.parallel.experts import PolyNorm

    if config.get("hidden_act") != "poly_norm":
        return None
    return PolyNorm(
        module.param(prefix + "poly_weight", nn.initializers.constant(1 / 3),
                     stack + (3,), jnp.float32),
        module.param(prefix + "poly_bias", nn.initializers.zeros,
                     stack + (1,), jnp.float32),
        scale=config["polynorm_output_scale"],
        clamp=config["polynorm_bias_clamp"], eps=config["rms_norm_eps"])


class DenseFeedForward(nn.Module):
    config: Any

    @nn.compact
    def __call__(self, x, valid):
        from mmlspark_tpu.parallel.experts import swiglu

        c = self.config
        h, w, dtype = x.shape[-1], c["intermediate_size"], lm_dtype(c)
        shape = nn.initializers.normal(0.02)
        return swiglu(x, self.param("gate_proj", shape, (h, w), dtype),
                      self.param("up_proj", shape, (h, w), dtype),
                      self.param("down_proj", shape, (w, h), dtype),
                      dtype=dtype, limit=c.get("swiglu_limit"),
                      poly=_poly_norm(self, c)), None


class ExpertFeedForward(nn.Module):
    """Sparse experts (``parallel/experts.py``): the router scores all
    published experts, the experts held here serve their pairs, the
    shared expert (where ``n_shared_experts`` is not 0) every token.
    Also returns ``(pairs a held expert, dropped pairs)``."""

    config: Any

    @nn.compact
    def __call__(self, x, valid):
        from mmlspark_tpu.parallel import experts

        c = self.config
        first, count, routed = experts_held(c)
        h, w, dtype = x.shape[-1], c["moe_intermediate_size"], lm_dtype(c)
        limit, shape = c.get("swiglu_limit"), nn.initializers.normal(0.02)
        tokens = x.reshape(-1, h)
        with jax.named_scope("lm.moe.route"):
            router = self.param("router", shape, (h, routed), jnp.float32)
            bias = (jnp.zeros((routed,), jnp.float32)
                    if _setting(c, "selection_bias") is False else
                    self.param("router_bias", nn.initializers.zeros,
                               (routed,), jnp.float32))
            routing = experts.route(tokens, router, bias,
                                    top_k=c["num_experts_per_tok"],
                                    scale=c["routed_scaling_factor"])
        y, pairs, dropped = experts.grouped_experts(
            tokens, routing, valid.reshape(-1),
            self.param("experts_gate", shape, (count, h, w), dtype),
            self.param("experts_up", shape, (count, h, w), dtype),
            self.param("experts_down", shape, (count, w, h), dtype),
            held=(first, count), n=routed, dtype=dtype, limit=limit,
            tile=experts.tile_rows(tokens.shape[0],
                                   c["num_experts_per_tok"], routed),
            poly=_poly_norm(self, c, "experts_", (count,)))
        ws = w * c.get("n_shared_experts", 1)
        if ws:
            with jax.named_scope("lm.moe.shared"):
                y = y + experts.swiglu(
                    tokens, self.param("shared_gate", shape, (h, ws), dtype),
                    self.param("shared_up", shape, (h, ws), dtype),
                    self.param("shared_down", shape, (ws, h), dtype),
                    dtype=dtype, limit=limit,
                    poly=_poly_norm(self, c, "shared_"))
        return y.reshape(x.shape), (pairs, dropped)


class HyperConnection(nn.Module):
    """One sub-layer's leaves of the hyper-connection residual path
    (``parallel/hyper.py``) and its first pass over the streams ``x``
    (``hc_mult`` arrays ``(B, T, hidden)``): ``(what the sub-layer
    reads (B, T, hidden), (H_post, H_res))``, the pair being what
    ``hyper.hc_write`` takes with the sub-layer's output. The stream's
    own RMSNorm has no learned scale and the model's ``rms_norm_eps``;
    the leaves are declared float32 and multiplied in float32
    whatever dtype the engine placed them in."""

    config: Any

    @nn.compact
    def __call__(self, x):
        from mmlspark_tpu.parallel import hyper

        c = self.config
        n, width = len(x), x[0].shape[-1]
        zeros = nn.initializers.zeros
        h_pre, h_post, h_res = hyper.hc_coefficients(
            x, self.param("phi", nn.initializers.normal(0.02),
                          (n * width, 2 * n + n * n), jnp.float32),
            self.param("alpha", nn.initializers.constant(0.01), (3,),
                       jnp.float32),
            self.param("b_pre", zeros, (n,), jnp.float32),
            self.param("b_post", zeros, (n,), jnp.float32),
            self.param("b_res", zeros, (n, n), jnp.float32),
            norm_eps=c["rms_norm_eps"], iters=c["hc_sinkhorn_iters"],
            eps=_setting(c, "hc_eps"),
            clamp=(_setting(c, "mhc_h_res_clamp_min"),
                   _setting(c, "mhc_h_res_clamp_max")))
        return hyper.hc_read(x, h_pre), (h_post, h_res)


class HybridBlock(nn.Module):
    """One layer: ``h = h + mixer(pre(h))``, then the same around the
    feed-forward; where the config says ``layernorm_type`` ``pre_post``
    a sub-layer's output is normed too before it is added. The layer's
    index chooses both sub-layers: latent attention where
    ``full_attention_layers`` lists it (or in every layer where the
    config has no such key), else the delta rule; a dense SwiGLU under
    ``first_k_dense_replace``, else the experts.

    Where the config has ``hc_mult`` the residual is that many streams
    (``h``: a tuple of ``(B, T, hidden)`` arrays) and the sum is a
    :class:`HyperConnection`'s: the sub-layer reads a mix of the
    streams and its output is written back into each beside a mix of
    them all (``parallel/hyper.py``), under the scope ``lm.hc``, which
    stands outside the sub-layer's own.

    Where the family's ``attention`` is ``kv`` every mixer is a
    :class:`KVMixer` (scope ``lm.kv``), full or over a window by
    ``hybrid_layer_pattern``, and ``moe_layer_freq`` (a list) says which
    layers are sparse; both by the published index the layer stands
    for (``published_layers``)."""

    config: Any
    index: int

    def published(self) -> int:
        """The published index the layer stands for (``published_layers``,
        a cut's; its own where absent)."""
        c = self.config
        return c.get("published_layers",
                     range(c["num_hidden_layers"]))[self.index]

    def kv(self) -> bool:
        family = FAMILY_DEFAULTS.get(self.config.get("model_type"), {})
        return family.get("attention") == "kv"

    def latent(self) -> bool:
        listed = self.config.get("full_attention_layers")
        return listed is None or self.index in tuple(listed)

    def sparse(self) -> bool:
        freq = self.config.get("moe_layer_freq")
        if isinstance(freq, (list, tuple)):
            return bool(freq[self.published()])
        return self.index >= self.config["first_k_dense_replace"]

    def window(self) -> bool:
        """A layer of window attention: where the config has
        ``hybrid_layer_pattern``, its 1 at the published index;
        elsewhere where ``use_sliding_window`` says so, from
        ``max_window_layers`` on every layer but each
        ``sliding_window_period``-th ("interleave": published index + 1 a
        multiple of the period is full)."""
        c = self.config
        pattern = c.get("hybrid_layer_pattern")
        if pattern is not None:
            return pattern[self.published()] == 1
        if not c.get("use_sliding_window"):
            return False
        index = self.published()
        return (index >= c["max_window_layers"]
                and (index + 1) % c["sliding_window_period"] != 0)

    def mixer(self):
        """The mixer: the delta rule, latent attention, or where the
        config says ``attention_cls`` ``gdla`` its grouped differential
        form, over a window where ``window()``; where the family's
        ``attention`` is ``kv``, a :class:`KVMixer`."""
        if self.kv():
            return functools.partial(KVMixer, window=self.window())
        if not self.latent():
            return DeltaMixer
        if self.config.get("attention_cls") != "gdla":
            return LatentMixer
        return functools.partial(DiffLatentMixer, window=self.window())

    def init_state(self, batch, capacity):
        """The mixer's empty state: a window layer's ring, else the
        cache or the recurrent state."""
        if self.kv():
            return KVMixer.init_state(self.config, batch, capacity,
                                      self.window())
        if self.window():
            return DiffLatentMixer.ring(self.config, batch)
        return (LatentMixer if self.latent() else DeltaMixer).init_state(
            self.config, batch, capacity)

    @nn.compact
    def __call__(self, h, positions, lengths, state):
        from mmlspark_tpu.parallel import hyper

        c = self.config
        post = _setting(c, "layernorm_type") == "pre_post"
        streams = "hc_mult" in c

        def around(h, scope, name, sub, *args):
            """``(h after the sub-layer, what it returned beside its
            output)``."""
            x, mix = h, None
            if streams:
                with jax.named_scope("lm.hc"):
                    x, mix = HyperConnection(c, name=f"{name}_hc")(h)
            with jax.named_scope(scope):
                y, out = sub(c, name=name)(
                    block_norm(self, f"{name}_pre", x, c), *args)
                if post:
                    y = block_norm(self, f"{name}_post", y, c)
                if not streams:
                    return clamped(h + y), out
            with jax.named_scope("lm.hc"):
                return clamped(hyper.hc_write(h, y, *mix)), out

        def clamped(h):
            # the residual held to +-hidden_clamp after each sub-layer
            bound = c.get("hidden_clamp")
            if bound is None:
                return h
            return jax.tree_util.tree_map(
                lambda s: jnp.clip(s, -bound, bound), h)

        h, state = around(
            h, "lm.kv" if self.kv() else "lm.mla" if self.latent()
            else "lm.gdn", "mixer", self.mixer(), positions, lengths, state)
        scope = "lm.moe" if self.sparse() else "lm.mlp"
        with jax.named_scope(scope):
            valid = (jnp.arange(positions.shape[1])[None, :]
                     < lengths[:, None])
        h, served = around(
            h, scope, "ffn",
            ExpertFeedForward if self.sparse() else DenseFeedForward, valid)
        return h, state, served


class HybridLM(nn.Module):
    """Causal language model: an embedding, the config's layers
    (``_blocks``: :class:`DecoderBlock` where the family's ``block`` is
    ``retention``, else :class:`HybridBlock`), a final norm and a head.

    ``apply(params, ids, lengths, state) -> (logits_last, state)``:
    ``ids`` ``(B, T)`` are the next ``T`` tokens of each sequence,
    ``lengths`` ``(B,)`` how many of them are real (padding follows
    them and leaves the state alone); ``state`` is
    ``lm_init_state(config, B, capacity)`` or what an earlier call
    returned. ``logits_last`` ``(B, vocab)`` float32 are the logits
    after each row's last real token of this call (undefined for a row
    with none). ``method="hidden"`` stops before the head,
    ``method="head"`` is the final norm and the head alone, so a prompt
    fed in several calls pays for the head once.

    The state holds ``pos``, the tokens each row has absorbed;
    ``layers``, each layer's own: a retention or delta-rule layer's
    fixed recurrent state (float32), a latent or key/value layer's
    cache of ``capacity`` positions, a window layer's ring; and where
    some layer is sparse ``experts``, what the expert layers served
    since the state was empty: ``pairs`` ``(expert layers, experts
    held)`` and ``dropped``. ``counts`` reads from a state what the
    stage reports.

    With ``hc_mult`` in the config the residual between the layers is
    that many streams a token: the embedding is copied into each before
    the first layer and they are summed after the last (of each row's
    last real token alone, unless ``every``), so what ``hidden``
    returns, and ``hidden_in_groups`` with it, has the one shape."""

    config: Any

    GROUP_TOKENS = 4096

    @staticmethod
    def _blocks(config):
        family = FAMILY_DEFAULTS.get(config.get("model_type", "brumby"), {})
        block = (DecoderBlock if family.get("block") == "retention"
                 else HybridBlock)
        return [block(config, i, name=f"layers_{i}")
                for i in range(config["num_hidden_layers"])]

    @staticmethod
    def init_state(config, batch, capacity=0):
        blocks = HybridLM._blocks(config)
        state = {"pos": jnp.zeros((batch,), jnp.int32),
                 "layers": [block.init_state(batch, capacity)
                            for block in blocks]}
        sparse = sum(block.sparse() for block in blocks)
        if sparse:
            _, count, _ = experts_held(config)
            state["experts"] = {
                "pairs": jnp.zeros((sparse, count), jnp.int32),
                "dropped": jnp.zeros((), jnp.int32)}
        return state

    @staticmethod
    def counts(config, state):
        """What a state says its rows went through, counted on the
        device: with expert layers, what they served (``expert_pairs``,
        one row: a count an expert layer and held expert;
        ``dropped_pairs``); with caches (latent or key/value), each
        row's ``cache_positions`` (the positions it has filled, summed
        over the layers that cache; a window layer's ring is no such
        cache) and ``cache_capacity`` (the same at capacity); with
        window layers, each row's ``window_positions``
        (``latent.window_read``, summed over them); with a residual of
        several streams, each row's ``hc_sublayer_tokens``: the tokens
        it has absorbed, prompt and generated, times the sub-layers they
        went through that path around."""
        from mmlspark_tpu.parallel import latent

        out, pos = {}, state["pos"]
        if "experts" in state:
            out.update(expert_pairs=state["experts"]["pairs"].reshape(1, -1),
                       dropped_pairs=state["experts"]["dropped"])
        caches = [(layer["c"] if "c" in layer else layer["v"]).shape[1]
                  for layer in state["layers"] if "c" in layer or "v" in layer]
        if caches:
            out.update(cache_positions=len(caches) * pos,
                       cache_capacity=jnp.full_like(pos, sum(caches)))
        rings = [next(iter(layer["ring"].values())).shape[1]
                 for layer in state["layers"] if "ring" in layer]
        if rings:
            out["window_positions"] = sum(
                latent.window_read(pos, width) for width in rings)
        if "hc_mult" in config:
            out["hc_sublayer_tokens"] = 2 * len(state["layers"]) * pos
        return out

    @staticmethod
    def row_groups(rows, t):
        """How many groups of rows a stretch of ``t`` tokens a row is
        absorbed in: the fewest (a power of two that divides the rows)
        that hold ``GROUP_TOKENS`` tokens each, or as near as the rows
        divide."""
        groups = 1
        while (rows * t > groups * HybridLM.GROUP_TOKENS
               and rows % (2 * groups) == 0):
            groups *= 2
        return groups

    @staticmethod
    def active_groups(lengths, groups):
        """``(first, stop)``: the range of the ``groups`` groups of
        consecutive rows from the first to the last that has a row with
        a real token (``lengths > 0``); ``(0, 0)`` where none has.
        ``lengths`` is a ``numpy`` array or a traced one, and so are
        the bounds."""
        active = (lengths.reshape(groups, -1) > 0).any(axis=1)
        return active.argmax(), (active * np.arange(1, groups + 1)).max()

    @staticmethod
    def hidden_in_groups(module, params, ids, lengths, state):
        """``method="hidden"`` a group of rows at a time, so that a
        stretch of many tokens (a prefill step of a wide batch) holds
        the activations of ``GROUP_TOKENS`` tokens and not of all: the
        state is the loop's carry, a group's rows are cut from it and
        written back in place, and the experts' counters pass from
        group to group. The loop runs from the first group with a real
        token to the last (``active_groups``): a group outside it has
        nothing to absorb, so its state stays the carry's and its
        hidden rows zero. The result is the same for rows in any
        order; sorted by length (``length_batches``), the rows that
        have ended are whole groups at one end. One group (a decode
        step) is the plain call. The cuts, the pastes and the loop's
        bounds stand under the scope ``lm.group``."""
        rows, t = ids.shape
        groups = HybridLM.row_groups(rows, t)
        if groups == 1:
            return module.apply(params, ids, lengths, state, method="hidden")
        per = rows // groups

        def body(g, carry):
            state, out = carry

            def cut(x):
                return jax.lax.dynamic_slice_in_dim(x, g * per, per, axis=0)

            def paste(whole, part):
                return jax.lax.dynamic_update_slice_in_dim(
                    whole, part, g * per, axis=0)

            with jax.named_scope("lm.group"):
                mine = {k: v if k == "experts" else
                        jax.tree_util.tree_map(cut, v)
                        for k, v in state.items()}
                group_ids, group_lengths = cut(ids), cut(lengths)
            h, mine = module.apply(params, group_ids, group_lengths, mine,
                                   method="hidden")
            with jax.named_scope("lm.group"):
                state = {k: mine[k] if k == "experts" else
                         jax.tree_util.tree_map(paste, v, mine[k])
                         for k, v in state.items()}
                return state, paste(out, h)

        hidden = module.config["hidden_size"]
        with jax.named_scope("lm.group"):
            first, stop = HybridLM.active_groups(lengths, groups)
            out = jnp.zeros((rows, hidden), jnp.float32)
        return jax.lax.fori_loop(first, stop, body, (state, out))[::-1]

    def setup(self):
        c = self.config
        self.embedding = self.param(
            "embedding", nn.initializers.normal(0.02),
            (c["vocab_size"], c["hidden_size"]), lm_dtype(c))
        self.layers = self._blocks(c)
        self.final_norm = norm_weight(self, "final_norm", c["hidden_size"],
                                      c)
        self.lm_head = Linear(c["vocab_size"], lm_dtype(c))

    def hidden(self, ids, lengths, state, every=False):
        """Residual after the last layer at each row's last real token
        ``(B, hidden)``, or with ``every`` at all ``T`` positions."""
        with jax.named_scope("lm.embed"):
            h = jnp.take(self.embedding, ids, axis=0).astype(jnp.float32)
        streams = "hc_mult" in self.config
        if streams:
            h = (h,) * self.config["hc_mult"]
        positions = state["pos"][:, None] + jnp.arange(ids.shape[1])
        layers, pairs = [], []
        experts = dict(state.get("experts", {}))
        for block, layer_state in zip(self.layers, state["layers"]):
            h, layer_state, served = block(h, positions, lengths,
                                           layer_state)
            layers.append(layer_state)
            if served is not None:
                pairs.append(served[0])
                experts["dropped"] = experts["dropped"] + served[1]
        if not every:
            with jax.named_scope("lm.last"):
                last = jnp.clip(lengths - 1, 0, ids.shape[1] - 1)

                def at_last(x):
                    return jnp.take_along_axis(x, last[:, None, None],
                                               axis=1)[:, 0]

                h = jax.tree_util.tree_map(at_last, h)
        if streams:
            with jax.named_scope("lm.hc"):
                h = sum(h)
        if pairs:
            experts["pairs"] = experts["pairs"] + jnp.stack(pairs)
        state = {"pos": state["pos"] + lengths, "layers": layers}
        return h, dict(state, experts=experts) if experts else state

    def head(self, h):
        with jax.named_scope("lm.head"):
            return self.lm_head(model_norm(h, self.final_norm,
                                           self.config))

    def __call__(self, ids, lengths, state, every=False):
        h, state = self.hidden(ids, lengths, state, every)
        return self.head(h), state
