"""Flax backbone zoo for the deep-learning estimators.

The reference fine-tunes torchvision/HF checkpoints pulled from the
network (dl/DeepVisionClassifier.py backbone param). This environment is
zero-egress, so the zoo is built in-repo: a compact ResNet family and a
transformer encoder, both TPU-shaped (NHWC convs, bf16-friendly widths,
optional ring attention for long sequences), and a causal decoder
(:class:`RetentionLM`: RMSNorm, rotary positions, grouped heads, SwiGLU)
whose layers mix the sequence by power retention and carry a state
from one forward pass to the next.
"""

from __future__ import annotations

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
    """One decoder layer: pre-norm power retention over grouped heads
    (``parallel/retention.py``), then pre-norm SwiGLU. ``config`` holds
    the keys of the model's ``config.json``."""

    config: Any

    @nn.compact
    def __call__(self, h, positions, lengths, state):
        from mmlspark_tpu.parallel import retention

        c = self.config
        heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
        d, eps, dtype = c["head_dim"], c["rms_norm_eps"], lm_dtype(c)
        b, t, _ = h.shape

        def rounded(x):
            # q, k, v carry the model's precision but stay float32 for
            # the state's arithmetic; reduce_precision is the rounding
            # that XLA may not drop (a float32 -> bfloat16 -> float32
            # pair of converts it may, as excess precision)
            info = jnp.finfo(dtype)
            return jax.lax.reduce_precision(x, info.nexp, info.nmant)

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
            q, k, v = rounded(q), rounded(k), rounded(v)
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
        return h, state


def lm_init_state(config: Mapping[str, Any], batch: int):
    """An empty state for ``batch`` sequences: no token absorbed, every
    layer's retention state zero (float32, whatever the model's dtype)."""
    from mmlspark_tpu.parallel import retention

    return {"pos": jnp.zeros((batch,), jnp.int32),
            "layers": [retention.init_state(
                batch, config["num_key_value_heads"], config["head_dim"])
                for _ in range(config["num_hidden_layers"])]}


def lm_state_bytes(config: Mapping[str, Any], batch: int) -> int:
    """Bytes of ``lm_init_state(config, batch)``."""
    from mmlspark_tpu.parallel import retention

    shapes = retention.state_shapes(batch, config["num_key_value_heads"],
                                    config["head_dim"])
    per_layer = sum(4 * int(np.prod(shape)) for shape in shapes.values())
    return config["num_hidden_layers"] * per_layer + 4 * batch


def lm_init_params(config: Mapping[str, Any], seed: int = 0):
    """Freshly initialised parameters of ``RetentionLM(config)``."""
    return RetentionLM(dict(config)).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 2), jnp.int32),
        jnp.full((1,), 2, jnp.int32), lm_init_state(config, 1))


def lm_param_shapes(config: Mapping[str, Any]):
    """The same pytree as shapes and dtypes, without materialising it."""
    return jax.eval_shape(lambda: lm_init_params(config))


class RetentionLM(nn.Module):
    """Causal language model of :class:`DecoderBlock` layers.

    ``apply(params, ids, lengths, state) -> (logits_last, state)``:
    ``ids`` ``(B, T)`` are the next ``T`` tokens of each sequence,
    ``lengths`` ``(B,)`` how many of them are real (padding follows
    them and leaves the state alone); ``state`` is
    ``lm_init_state(config, B)`` or what an earlier call returned (``pos``: tokens absorbed so far;
    ``layers``: each layer's retention state, float32). ``logits_last``
    ``(B, vocab)`` float32 are the logits after each row's last real
    token of this call (undefined for a row with none).
    ``method="hidden"`` stops before the head, ``method="head"`` is the
    final norm and the head alone, so a prompt fed in several calls
    pays for the head once."""

    config: Any

    def setup(self):
        c = self.config
        self.embedding = self.param(
            "embedding", nn.initializers.normal(0.02),
            (c["vocab_size"], c["hidden_size"]), lm_dtype(c))
        self.layers = [DecoderBlock(c, name=f"layers_{i}")
                       for i in range(c["num_hidden_layers"])]
        self.final_norm = self.param("final_norm", nn.initializers.ones,
                                     (c["hidden_size"],), jnp.float32)
        self.lm_head = Linear(c["vocab_size"], lm_dtype(c))

    def hidden(self, ids, lengths, state, every=False):
        """Residual after the last layer at each row's last real token
        ``(B, hidden)``, or with ``every`` at all ``T`` positions."""
        with jax.named_scope("lm.embed"):
            h = jnp.take(self.embedding, ids, axis=0).astype(jnp.float32)
        positions = state["pos"][:, None] + jnp.arange(ids.shape[1])
        layers = []
        for block, layer_state in zip(self.layers, state["layers"]):
            h, layer_state = block(h, positions, lengths, layer_state)
            layers.append(layer_state)
        if not every:
            last = jnp.clip(lengths - 1, 0, ids.shape[1] - 1)
            h = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        return h, {"pos": state["pos"] + lengths, "layers": layers}

    def head(self, h):
        with jax.named_scope("lm.head"):
            return self.lm_head(rms_norm(h, self.final_norm,
                                         self.config["rms_norm_eps"]))

    def __call__(self, ids, lengths, state, every=False):
        h, state = self.hidden(ids, lengths, state, every)
        return self.head(h), state
