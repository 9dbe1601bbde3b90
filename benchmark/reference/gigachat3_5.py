"""Plain hybrid decoder (GigaChat3.5-432B-A28B's layers): ``jax.numpy``
in float32 over one whole sequence, no state, no chunks, no cache, no
kernels, no code of the program.

What is implemented (sizes and switches from the model's
``config.json``; ``x`` is a sub-layer's input after its pre-norm, and a
layer is ``h += post(mixer(pre(h)))``, then the same around the
feed-forward, ``layernorm_type`` ``pre_post``):

*Norm*: ``x / sqrt(mean(x^2) + eps) * 2 sigmoid(w)``, ``w`` stored
centred at zero.

*Delta-rule mixer* (layers not in ``full_attention_layers``; Gated
DeltaNet, arXiv:2412.06464), token by token:
    q, k = W_q x, W_k x (key heads x d_k); v, z = W_v x, W_z x (value
    heads x d_v); b, a = W_b x, W_a x (one a value head)
    (q, k, v) <- silu(causal depth-wise convolution of width 4)
    q, k L2-normalised a head, q scaled by d_k ** -0.5; value head i
    reads key head i // (value heads / key heads)
    beta = sigmoid(b); log g = -exp(A_log) softplus(a + dt_bias)
    S <- g S;  S <- S + k (beta (v - S^T k))^T;  o = S^T q
    y = W_o (norm(o) * 2 sigmoid(z)), the norm over a head's d_v.

*Latent-attention mixer* (DeepSeek-V2, arXiv:2405.04434), expanded
over the whole sequence:
    c_q = norm(W_dq x); q = W_uq c_q (heads x (nope + rope))
    [c_kv; k_r] = W_dkv x; c_kv = norm(c_kv); [k_n; v] = W_ukv c_kv
    rotary on q's rope part and on k_r (shared by the heads): element
    2 j turns with 2 j + 1, YaRN frequencies
    scores = (q_n . k_n + q_r . k_r) (nope + rope) ** -0.5 m ** 2,
    m = 0.1 ln factor + 1; causal softmax; o = sum p v
    y = W_o (o * sigmoid(W_g x)).

*Expert layer* (layers from ``first_k_dense_replace`` on; DeepSeek-V3,
arXiv:2412.19437): ``s = sigmoid(W_r x)`` over all ``router_experts``
in float32; the ``num_experts_per_tok`` largest of ``s + bias``; weights
``routed_scaling_factor s_i / sum of the chosen s``; ``y = sum w_i
E_i(x) + E_shared(x)`` over the chosen experts held here
(``experts_held``: the others' part is left out, as in the program),
as a loop over the experts held with a mask. An expert, the shared one
and the dense layers' feed-forward are ``W_d (silu(min(W_g x, limit)) *
clip(W_u x, +-limit))``, ``limit = swiglu_limit``.

Everything the config does not settle is under ``assumed`` in the
configuration's file. ``precision`` as in ``reference/brumby.py``:
``highest`` (float32 throughout), ``bfloat16`` (what the configuration
states: both operands of every product with a weight matrix rounded to
bfloat16, float32 accumulation; the delta rule's q, k and v, and the
attention's queries, keys, values and softmax weights rounded to
bfloat16; norms, gates, rotation, softmax, router and the delta-rule
state float32 at highest precision), ``float8_weights`` and ``float8``
(the precisions below it, which the cell's limits have to refuse).

A top-k choice can flip on rounding where a score nearly ties with
the boundary of the choice, and the flipped position then differs by
tens of per cent. ``logits(..., margins=True)`` therefore also returns
each position's least routing margin over the expert layers and the
experts held: how far a held expert's ``s + bias`` lies from leaving
the chosen ``k`` (above the ``(k+1)``-th largest) or from entering them
(below the ``k``-th largest), so that a comparison of maxima can leave
out the positions where a held expert's part hangs on rounding. A flip
between two experts held elsewhere moves only the normaliser, by the
difference of two nearly tied scores, and is not counted.

Weights: ``{"embed", "final_norm", "head", "layers": [layer, ...]}``;
a layer is ``{"mixer_pre", "mixer_post", "ffn_pre", "ffn_post",
"mixer": {...}, "ffn": {...}}`` with the names used below, matrices
stored ``(in, out)``.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "bfloat16", "float8_weights", "float8")
HEAD_BLOCK = 16384
HI = jax.lax.Precision.HIGHEST


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
        return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                          precision=HI)
    if precision == "float8":       # exact products of 8-bit operands
        return jnp.matmul(_round(x, precision), _round(w, precision),
                          precision=HI)
    if precision == "float8_weights":
        w = _round(w, "float8")
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def norm(x, w, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * 2.0 * jax.nn.sigmoid(w.astype(jnp.float32)))


def swiglu(x, w_gate, w_up, w_down, limit, precision):
    g, u = _matmul(x, w_gate, precision), _matmul(x, w_up, precision)
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return _matmul(jax.nn.silu(g) * u, w_down, precision)


# -- delta rule --------------------------------------------------------


def delta_rule(q, k, v, log_g, beta):
    """The recurrence, a token at a time. ``q``, ``k``: ``(T, heads,
    d_k)`` (already a row a value head); ``v``: ``(T, heads, d_v)``;
    ``log_g``, ``beta``: ``(T, heads)``. Returns ``(T, heads, d_v)``."""

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, s,
                                             precision=HI))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=HI)

    first = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, first, (q, k, v, log_g, beta))[1]


def delta_mixer(x, m, cfg, precision):
    kh, vh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    d_k, d_v = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width, t = cfg["linear_conv_kernel_dim"], x.shape[0]
    mixed = jnp.concatenate([_matmul(x, m[n], precision)
                             for n in ("wq", "wk", "wv")], axis=-1)
    padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(m["conv"][j].astype(jnp.float32)
                            * padded[j:j + t] for j in range(width)))
    q, k, v = jnp.split(mixed, [kh * d_k, 2 * kh * d_k], axis=-1)

    def unit(a):
        a = a.reshape(t, kh, d_k)
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    group = vh // kh
    q = jnp.repeat(_round(unit(q) * d_k ** -0.5, precision), group, axis=1)
    k = jnp.repeat(_round(unit(k), precision), group, axis=1)
    v = _round(v.reshape(t, vh, d_v), precision)
    beta = jax.nn.sigmoid(_matmul(x, m["wb"], precision))
    log_g = (-jnp.exp(m["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        _matmul(x, m["wa"], precision) + m["dt_bias"].astype(jnp.float32)))
    o = norm(delta_rule(q, k, v, log_g, beta), m["o_norm"],
             cfg["linear_attn_o_norm_eps"])
    z = _matmul(x, m["wz"], precision).reshape(t, vh, d_v)
    return _matmul((o * 2.0 * jax.nn.sigmoid(z)).reshape(t, vh * d_v),
                   m["wo"], precision)


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
    """``x``: ``(T, ..., dim)`` at positions ``0 .. T - 1``. Element
    ``2 j`` turns with ``2 j + 1`` (``rope_interleave``), or ``j`` with
    ``j + dim / 2`` without it."""
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


def latent_mixer(x, m, cfg, precision):
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    d_v, eps, t = cfg["v_head_dim"], cfg["rms_norm_eps"], x.shape[0]
    scaling = cfg.get("rope_scaling") or {}
    freq = yarn_frequencies(rope, cfg["rope_theta"], scaling)
    interleave = cfg.get("rope_interleave", True)
    scale = (nope + rope) ** -0.5
    if scaling and cfg.get("use_mla_scaling_factor", False):
        scale *= (0.1 * scaling.get("mscale_all_dim", 1)
                  * math.log(scaling["factor"]) + 1.0) ** 2

    c_q = norm(_matmul(x, m["w_dq"], precision), m["q_norm"], eps)
    q = _matmul(c_q, m["w_uq"], precision).reshape(t, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope],
                         rotary(q[..., nope:], freq, interleave)], axis=-1)
    down = _matmul(x, m["w_dkv"], precision)
    # what a cache would hold: the normed latent and the rotated key
    c_kv = _round(norm(down[:, :rank], m["kv_norm"], eps), precision)
    k_r = _round(rotary(down[:, rank:], freq, interleave), precision)
    kv = _matmul(c_kv, m["w_ukv"].reshape(rank, heads * (nope + d_v)),
                 precision).reshape(t, heads, nope + d_v)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r[:, None, :], (t, heads, rope))], axis=-1)
    q, k, v = (_round(a, precision) for a in (q, k, kv[..., nope:]))
    scores = jnp.einsum("thd,shd->hts", q, k, precision=HI) * scale
    mask = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", _round(p, precision), v, precision=HI)
    o = o.reshape(t, heads * d_v)
    if cfg.get("gated_attention", False):
        o = o * jax.nn.sigmoid(_matmul(x, m["w_gate"], precision))
    return _matmul(o, m["wo"], precision)


# -- feed-forward ------------------------------------------------------


def held_experts(cfg):
    count = cfg["n_routed_experts"]
    first, stop = cfg.get("experts_held", (0, count))
    return first, stop - first


def expert_layer(x, m, cfg, precision):
    """``(y, margin)``: the held experts' and the shared expert's part
    of the layer, and each position's routing margin."""
    top_k, limit = cfg["num_experts_per_tok"], cfg.get("swiglu_limit")
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), m["router"].astype(jnp.float32),
        precision=HI))
    ranked, chosen = jax.lax.top_k(
        scores + m["router_bias"].astype(jnp.float32), top_k + 1)
    chosen = chosen[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    weights = picked / picked.sum(axis=1, keepdims=True) \
        if cfg.get("norm_topk_prob", True) else picked
    weights = cfg["routed_scaling_factor"] * weights
    first, count = held_experts(cfg)
    y = swiglu(x, m["shared_gate"], m["shared_up"], m["shared_down"], limit,
               precision)

    def add_expert(e, y):                   # every token through every
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0),
                       axis=1)              # held expert, then the mask
        return y + mine[:, None] * swiglu(
            x, *(jnp.asarray(m[name])[e] for name in (
                "experts_gate", "experts_up", "experts_down")),
            limit, precision)

    # a loop the compiler keeps rolled: one expert's program, not 16
    y = jax.lax.fori_loop(0, count, add_expert, y)
    # a held expert's distance to the boundary of the choice: chosen,
    # above the (k+1)-th largest; not chosen, below the k-th largest
    mine = (scores + m["router_bias"].astype(jnp.float32))[
        :, first:first + count]
    last_in, first_out = ranked[:, top_k - 1:top_k], ranked[:, top_k:]
    margin = jnp.where(mine >= last_in, mine - first_out, last_in - mine)
    return y, margin.min(axis=1)


# -- the model ---------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("sizes", "latent", "sparse", "precision"))
def _layer(h, layer, sizes, latent, sparse, precision):
    # one compiled program a kind of layer, so that only one layer's
    # float32 copy of its weights is alive at a time
    return layer_forward(h, layer, json.loads(sizes), latent, sparse,
                         precision)


def layer_forward(h, layer, cfg, latent, sparse, precision):
    """One layer over one sequence: latent attention or the delta rule,
    over the experts or a dense SwiGLU. ``h``: ``(T, hidden)`` float32.
    Returns ``(h, margin)``; ``margin`` is ``inf`` for a dense layer."""
    eps = cfg["rms_norm_eps"]
    mixer = latent_mixer if latent else delta_mixer
    h = h + norm(mixer(norm(h, layer["mixer_pre"], eps), layer["mixer"],
                       cfg, precision), layer["mixer_post"], eps)
    x = norm(h, layer["ffn_pre"], eps)
    if sparse:
        y, margin = expert_layer(x, layer["ffn"], cfg, precision)
    else:
        f = layer["ffn"]
        y = swiglu(x, f["w_gate"], f["w_up"], f["w_down"],
                   cfg.get("swiglu_limit"), precision)
        margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
    return h + norm(y, layer["ffn_post"], eps), margin


def hidden(weights, ids, cfg, precision="highest"):
    """``(final-norm output, least routing margin)`` at every position
    of one sequence."""
    h = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    sizes = json.dumps(cfg, sort_keys=True)
    margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
    for index, layer in enumerate(weights["layers"]):
        h, layer_margin = _layer(
            h, layer, sizes, index in tuple(cfg["full_attention_layers"]),
            index >= cfg["first_k_dense_replace"], precision)
        margin = jnp.minimum(margin, layer_margin)
    return norm(h, weights["final_norm"], cfg["rms_norm_eps"]), margin


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
    blocks = [_matmul(x, head[:, s:s + HEAD_BLOCK], precision)
              for s in range(0, head.shape[1], HEAD_BLOCK)]
    out = jnp.concatenate(blocks, axis=-1)
    return (out, margin) if margins else out
