"""Plain Kimi-K2 decoder (the DeepSeek-V3 layer: arXiv:2412.19437,
latent attention from arXiv:2405.04434): ``jax.numpy`` in float32 over
one whole sequence under ``jax.default_matmul_precision("highest")``,
no state, no chunks, no cache, no kernels, no code of the program.

One layer, for ``h`` of width ``hidden_size`` at position ``t``
(sizes from the model's ``config.json``):

    rms(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w
    x = rms(h; attn_norm)
    c_q = rms(x W_dq; q_norm);  q = c_q W_uq, heads x [q_n (nope); q_r (rope)]
    [c'; k'] = x W_dkv;  c = rms(c'; kv_norm);  k_r = rope(k', t), one for
    all heads;  q_r <- rope(q_r, t)
    head i: k_i = [c W_uk[i]; k_r], v_i = c W_uv[i]
    o_i = softmax_{s <= t}(scale q_i . k_i(s)) v_i(s)
    scale = (nope + rope) ** -0.5 * m ** 2, m = 0.1 mscale_all_dim
    ln(factor) + 1 where ``rope_scaling`` sets ``mscale_all_dim`` (the
    family's code; ``mscale`` = ``mscale_all_dim`` leaves cos and sin
    unscaled)
    h <- h + concat(o_i) W_o          (no gate, no norm after)
    y = rms(h; ffn_norm)
    layers under ``first_k_dense_replace``:
        h <- h + W_down (silu(y W_gate) * (y W_up))
    the others: s = sigmoid(y W_r) over all ``router_experts`` in
        float32; the ``num_experts_per_tok`` largest of s + bias are
        chosen (``n_group`` = ``topk_group`` = 1: no group limit) and
        weighed ``routed_scaling_factor`` s_e / sum of the chosen s;
        h <- h + sum_e w_e E_e(y) + E_shared(y), each E a SwiGLU of
        ``moe_intermediate_size`` with no clamp, the sum over the
        chosen experts in ``experts_held`` only (the others' part is
        left out, as in the program)
    after the last layer rms(h; final_norm) and the head.

``rope``: YaRN frequencies (arXiv:2309.00071); element ``2 j`` turns
with ``2 j + 1`` (the family's layout; ``rope_interleave`` false pairs
``j`` with ``j + rope / 2``).

``precision``: ``highest`` (float32 throughout), ``bfloat16`` (what the
configuration states: both operands of every product with a weight
matrix rounded to bfloat16, float32 accumulation; the cached latent and
rotated key, the attention's queries, keys, values and softmax weights
rounded to bfloat16; norms, rotation, softmax and the router float32 at
highest precision), ``float8_weights`` and ``float8`` (the precisions
below it, which a cell's limits have to refuse).

A top-k choice can flip on rounding where a score nearly ties with the
boundary of the choice. ``logits(..., margins=True)`` also returns each
position's least routing margin over the expert layers and the experts
held: how far a held expert's ``s + bias`` lies from leaving the chosen
``k`` (above the ``(k+1)``-th largest) or from entering them (below the
``k``-th largest).

Weights: ``{"embed", "final_norm", "head", "layers": [layer, ...]}``; a
layer is ``{"attn_norm", "ffn_norm", "mixer": {...}, "ffn": {...}}``
with the names used below, matrices stored ``(in, out)``.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "bfloat16", "float8_weights", "float8")
HEAD_BLOCK = 16384


def _round(x, precision):
    x = x.astype(jnp.float32)
    if precision == "highest":
        return x
    if precision == "float8":       # not "float8_weights": bfloat16 there
        unit = jnp.max(jnp.abs(x)) / 240.0 + 1e-30
        return jax.lax.reduce_precision(x / unit, exponent_bits=4,
                                        mantissa_bits=3) * unit
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _matmul(x, w, precision):
    """``x @ w`` in the named precision; float32 out."""
    if precision == "highest":
        return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32))
    if precision == "float8":       # exact products of 8-bit operands
        return jnp.matmul(_round(x, precision), _round(w, precision))
    if precision == "float8_weights":
        w = _round(w, "float8")
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def swiglu(x, w_gate, w_up, w_down, precision):
    return _matmul(jax.nn.silu(_matmul(x, w_gate, precision))
                   * _matmul(x, w_up, precision), w_down, precision)


# -- latent attention --------------------------------------------------


def yarn_frequencies(dim, theta, scaling):
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return base.astype(np.float32)
    original = scaling["original_max_position_embeddings"]

    def dimension_of(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dimension_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dimension_of(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (base / scaling["factor"] * ramp + base * (1 - ramp)).astype(
        np.float32)


def rotary(x, frequencies, interleave=True):
    """``x``: ``(T, ..., dim)`` at positions ``0 .. T - 1``."""
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * frequencies
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if not interleave:
        half = x.shape[-1] // 2
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling") or {}
    if scaling.get("mscale_all_dim"):
        scale *= (0.1 * scaling["mscale_all_dim"]
                  * math.log(scaling["factor"]) + 1.0) ** 2
    return scale


def latent_mixer(x, m, cfg, precision):
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d_v, eps, t = cfg["v_head_dim"], cfg["rms_norm_eps"], x.shape[0]
    freq = yarn_frequencies(rope, cfg["rope_theta"],
                            cfg.get("rope_scaling") or {})
    interleave = cfg.get("rope_interleave", True)

    c_q = rms(_matmul(x, m["w_dq"], precision), m["q_norm"], eps)
    q = _matmul(c_q, m["w_uq"], precision).reshape(t, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope],
                         rotary(q[..., nope:], freq, interleave)], axis=-1)
    down = _matmul(x, m["w_dkv"], precision)
    # what a cache would hold: the normed latent and the rotated key
    c_kv = _round(rms(down[:, :rank], m["kv_norm"], eps), precision)
    k_r = _round(rotary(down[:, rank:], freq, interleave), precision)
    kv = _matmul(c_kv, m["w_ukv"].reshape(rank, heads * (nope + d_v)),
                 precision).reshape(t, heads, nope + d_v)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r[:, None, :], (t, heads, rope))], axis=-1)
    q, k, v = (_round(a, precision) for a in (q, k, kv[..., nope:]))
    scores = jnp.einsum("thd,shd->hts", q, k) * softmax_scale(cfg)
    mask = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", _round(p, precision), v)
    return _matmul(o.reshape(t, heads * d_v), m["wo"], precision)


# -- feed-forward ------------------------------------------------------


def held_experts(cfg):
    count = cfg["n_routed_experts"]
    first, stop = cfg.get("experts_held", (0, count))
    return first, stop - first


def expert_layer(x, m, cfg, precision):
    """``(y, margin)``: the held experts' and the shared expert's part
    of the layer, and each position's routing margin."""
    top_k = cfg["num_experts_per_tok"]
    bias = m["router_bias"].astype(jnp.float32)
    scores = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                       m["router"].astype(jnp.float32)))
    ranked, chosen = jax.lax.top_k(scores + bias, top_k + 1)
    chosen = chosen[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    weights = picked / picked.sum(axis=1, keepdims=True) \
        if cfg.get("norm_topk_prob", True) else picked
    weights = cfg["routed_scaling_factor"] * weights
    first, count = held_experts(cfg)
    y = swiglu(x, m["shared_gate"], m["shared_up"], m["shared_down"],
               precision)

    def add_expert(e, y):                   # every token through every
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0),
                       axis=1)              # held expert, then the mask
        return y + mine[:, None] * swiglu(
            x, *(jnp.asarray(m[name])[e] for name in (
                "experts_gate", "experts_up", "experts_down")), precision)

    # a loop the compiler keeps rolled: one expert's program, not 12
    y = jax.lax.fori_loop(0, count, add_expert, y)
    # a held expert's distance to the boundary of the choice: chosen,
    # above the (k+1)-th largest; not chosen, below the k-th largest
    mine = (scores + bias)[:, first:first + count]
    last_in, first_out = ranked[:, top_k - 1:top_k], ranked[:, top_k:]
    margin = jnp.where(mine >= last_in, mine - first_out, last_in - mine)
    return y, margin.min(axis=1)


# -- the model ---------------------------------------------------------


def layer_forward(h, layer, cfg, sparse, precision):
    """One layer over one sequence. ``h``: ``(T, hidden)`` float32.
    Returns ``(h, margin)``; ``margin`` is ``inf`` for a dense layer."""
    eps = cfg["rms_norm_eps"]
    h = h + latent_mixer(rms(h, layer["attn_norm"], eps), layer["mixer"],
                         cfg, precision)
    y = rms(h, layer["ffn_norm"], eps)
    if sparse:
        y, margin = expert_layer(y, layer["ffn"], cfg, precision)
    else:
        f = layer["ffn"]
        y = swiglu(y, f["w_gate"], f["w_up"], f["w_down"], precision)
        margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
    return h + y, margin


@functools.partial(jax.jit, static_argnames=("sizes", "sparse", "precision"))
def _layer(h, layer, sizes, sparse, precision):
    # one compiled program a kind of layer, so that only one layer's
    # float32 copy of its weights is alive at a time
    with jax.default_matmul_precision("highest"):
        return layer_forward(h, layer, json.loads(sizes), sparse, precision)


def hidden(weights, ids, cfg, precision="highest"):
    """``(final-norm output, least routing margin)`` at every position
    of one sequence."""
    h = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    sizes = json.dumps(cfg, sort_keys=True)
    margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
    for index, layer in enumerate(weights["layers"]):
        h, layer_margin = _layer(h, layer, sizes,
                                 index >= cfg["first_k_dense_replace"],
                                 precision)
        margin = jnp.minimum(margin, layer_margin)
    return rms(h, weights["final_norm"], cfg["rms_norm_eps"]), margin


def logits(weights, ids, cfg, precision="highest", positions=None,
           margins=False):
    """``ids``: ``(T,)`` one sequence, or ``(n, T)``. Every position's
    logits, float32 ``(..., T, vocab)`` (``positions``: only those);
    with ``margins``, ``(logits, least routing margin a position)``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    ids = jnp.asarray(ids)
    if ids.ndim == 2:
        rows = [logits(weights, row, cfg, precision, positions, margins)
                for row in ids]
        if margins:
            return tuple(jnp.stack(part) for part in zip(*rows))
        return jnp.stack(rows)
    x, margin = hidden(weights, ids, cfg, precision)
    if positions is not None:
        x, margin = x[jnp.asarray(positions)], margin[jnp.asarray(positions)]
    head = weights["head"]
    with jax.default_matmul_precision("highest"):
        blocks = [_matmul(x, head[:, s:s + HEAD_BLOCK], precision)
                  for s in range(0, head.shape[1], HEAD_BLOCK)]
    out = jnp.concatenate(blocks, axis=-1)
    return (out, margin) if margins else out
