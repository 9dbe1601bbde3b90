"""Plain power-retention language model (Brumby-14B-Base's layer):
``jax.numpy`` in float32, the quadratic form over the whole sequence,
no state, no chunks, no cache, no code of the program.

What is implemented, for a token ``t`` with residual ``h_t``
("Scaling Context Requires Rethinking Attention", Manifest AI 2025,
arXiv:2507.04239; sizes from the model's ``config.json``):

    x = RMSNorm(h_t); q = W_q x; k = W_k x; v = W_v x
    per-head RMSNorm on q and k, rotary positions on both
    log g_t = log sigmoid(W_g x + b_g), one value a key-value head
    a[t, r] = exp(sum_{u=r+1..t} log g_u) * (s * q_t . k_r) ** p, r <= t
    y_t = sum_r a[t, r] v_r / (sum_r a[t, r] + eps)
    h' = h + W_o y; h'' = h' + W_down(silu(W_gate x') * W_up x')
    x' = RMSNorm(h'); a final RMSNorm and the untied head.

Query head ``i`` reads key-value head ``i // (heads / kv_heads)``.

Departures from the published description, and what ``config.json``
does not give (each is under ``assumed`` in the configuration's file):
- per-head RMSNorm on q and k and rotary positions at ``rope_theta``
  are kept from the Qwen3-14B checkpoint Brumby was retrained from
  (the config keeps ``rope_theta``); rotation pairs element ``j`` with
  ``j + head_dim / 2`` (the Hugging Face "rotate_half" convention);
- the gate is one sigmoid a key-value head from ``W_g`` (hidden x
  kv_heads) and a bias ``b_g``;
- the power ``p`` is 2 and the scale ``s`` is ``1 / sqrt(head_dim)``;
- the output is normalised by the summed weights, ``eps`` 1e-6 (for an
  even power every weight is non-negative, so the sum cannot cancel);
- weights come from a seed, not from the checkpoint.

``precision``:
- ``highest``: every array float32, every product at
  ``jax.default_matmul_precision("highest")`` (on a TPU a float32
  product is otherwise rounded to bfloat16);
- ``bfloat16``: the precision the configuration states. The operands
  of every product with a weight matrix are rounded to bfloat16 and
  accumulated in float32; q, k and v are rounded to bfloat16 after the
  norm and the rotation; norms, rotation, gate, the retention weights
  ``a`` and their sums stay float32 at highest precision;
- ``float8_weights``: the nearest precision below the stated one, which
  the cell's limits have to refuse: as ``bfloat16`` with every weight
  matrix first rounded to an 8-bit float (4 exponent and 3 mantissa
  bits, the tensor scaled by its largest magnitude);
- ``float8``: 8-bit floats wherever the configuration has bfloat16:
  both operands of every product with a weight matrix, and q, k and v.

Weights: ``{"embed": (V, H), "layers": [layer, ...], "final_norm": (H,),
"head": (H, V)}``; a layer is ``{"attn_norm", "wq", "wk", "wv", "wg",
"bg", "q_norm", "k_norm", "wo", "mlp_norm", "w_gate", "w_up",
"w_down"}``, matrices stored ``(in, out)``. A layer's weights are
touched a layer at a time and the head in blocks of the vocabulary, so
at the published widths the reference fits on the chip beside the
model.
"""

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bfloat16", "float8_weights", "float8")
HEAD_BLOCK = 16384


def _matmul(x, w, precision):
    """``x @ w`` in the named precision; float32 out."""
    if precision == "highest":
        return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if precision == "float8":       # exact products of 8-bit operands
        return jnp.matmul(_round(x, precision), _round(w, precision),
                          precision=jax.lax.Precision.HIGHEST)
    if precision == "float8_weights":
        w = _round(w, "float8")
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _round(x, precision):
    """What the configuration rounds to bfloat16 between two products
    (``reduce_precision``: a pair of converts XLA may drop as excess
    precision)."""
    x = x.astype(jnp.float32)
    if precision == "highest":
        return x
    if precision == "float8":       # not "float8_weights": bfloat16 there
        unit = jnp.max(jnp.abs(x)) / 240.0 + 1e-30
        return jax.lax.reduce_precision(x / unit, exponent_bits=4,
                                        mantissa_bits=3) * unit
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rotary(x, positions, theta):
    """``x``: ``(T, heads, dim)``; pairs ``j`` with ``j + dim / 2``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def retention(q, k, v, log_g, power, scale, eps):
    """The quadratic form. ``q``: ``(T, heads, dim)``; ``k``, ``v``:
    ``(T, kv_heads, dim)``; ``log_g``: ``(T, kv_heads)``. Returns
    ``(T, heads, dim)``."""
    t, heads, _ = q.shape
    group = heads // k.shape[1]
    hi = jax.lax.Precision.HIGHEST
    cum = jnp.cumsum(log_g, axis=0)                       # (T, kv)
    # sum_{u=r+1..t} log g_u = cum[t] - cum[r]
    decay = cum[:, None, :] - cum[None, :, :]             # (T, T, kv)
    mask = jnp.tril(jnp.ones((t, t), bool))
    out = []
    for i in range(heads):
        j = i // group
        score = scale * jnp.matmul(q[:, i, :], k[:, j, :].T, precision=hi)
        a = jnp.where(mask, jnp.exp(jnp.where(mask, decay[:, :, j], 0.0))
                      * score ** power, 0.0)              # (T, T)
        num = jnp.matmul(a, v[:, j, :], precision=hi)
        out.append(num / (jnp.sum(a, axis=1, keepdims=True) + eps))
    return jnp.stack(out, axis=1)


KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "retention_power", "retention_scale",
        "retention_eps")


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _layer(h, layer, sizes, precision):
    # one compiled program a layer shape, so that only one layer's
    # float32 copy of its weights is alive at a time
    return layer_forward(h, layer, dict(sizes), precision)


def layer_forward(h, layer, cfg, precision):
    """One layer over one sequence. ``h``: ``(T, hidden)`` float32."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t = h.shape[0]
    x = rms_norm(h, layer["attn_norm"], eps)
    q = _matmul(x, layer["wq"], precision).reshape(t, heads, dim)
    k = _matmul(x, layer["wk"], precision).reshape(t, kv, dim)
    v = _matmul(x, layer["wv"], precision).reshape(t, kv, dim)
    positions = jnp.arange(t)
    q = rotary(rms_norm(q, layer["q_norm"], eps), positions,
               cfg["rope_theta"])
    k = rotary(rms_norm(k, layer["k_norm"], eps), positions,
               cfg["rope_theta"])
    q, k, v = (_round(a, precision) for a in (q, k, v))
    log_g = jax.nn.log_sigmoid(
        _matmul(x, layer["wg"], precision)
        + layer["bg"].astype(jnp.float32))                # (T, kv)
    y = retention(q, k, v, log_g, cfg.get("retention_power", 2),
                  cfg.get("retention_scale", dim ** -0.5),
                  cfg.get("retention_eps", 1e-6))
    h = h + _matmul(y.reshape(t, heads * dim), layer["wo"], precision)
    x = rms_norm(h, layer["mlp_norm"], eps)
    gate = _matmul(x, layer["w_gate"], precision)
    up = _matmul(x, layer["w_up"], precision)
    return h + _matmul(jax.nn.silu(gate) * up, layer["w_down"], precision)


def hidden(weights, ids, cfg, precision="highest"):
    """Final-norm output at every position of one sequence."""
    h = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    sizes = tuple((k, cfg[k]) for k in KEYS if k in cfg)
    for layer in weights["layers"]:
        h = _layer(h, layer, sizes, precision)
    return rms_norm(h, weights["final_norm"], cfg["rms_norm_eps"])


def logits(weights, ids, cfg, precision="highest", positions=None):
    """``ids``: ``(T,)`` one sequence, or ``(n, T)``. Every position's
    logits, float32 ``(..., T, vocab)`` (``positions``: only those)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    ids = jnp.asarray(ids)
    if ids.ndim == 2:
        return jnp.stack([logits(weights, row, cfg, precision, positions)
                          for row in ids])
    x = hidden(weights, ids, cfg, precision)
    if positions is not None:
        x = x[jnp.asarray(positions)]
    head = weights["head"]
    blocks = [_matmul(x, head[:, s:s + HEAD_BLOCK], precision)
              for s in range(0, head.shape[1], HEAD_BLOCK)]
    return jnp.concatenate(blocks, axis=-1)
