"""Plain MiMo-V2 decoder: grouped-query attention in every layer, full
or over a window of ``sliding_window`` positions with a learned sink
logit a head, over a dense SwiGLU or sigmoid-routed experts.
``jax.numpy`` in float32 over one whole sequence under
``jax.default_matmul_precision("highest")``: no state, no chunks, no
cache, no ring, no kernels, no code of the program. The attention is
taken a block of ``Q_BLOCK`` queries at a time, a full layer's against
the whole sequence and a window layer's against the ``sliding_window``
positions before the block and the block's own, and every other part a
block of positions at a time, so that one row of 32k positions fits
beside the model.

One layer, for ``h`` of width ``hidden_size`` at position ``t`` (sizes
from the model's ``config.json``; a window layer's from its ``swa_``
keys):

    x = rms(h; attn_norm)
    [q | k | v] = x W_qkv: heads x head_dim, key heads x head_dim, key
        heads x v_head_dim
    the first r = int(partial_rotary_factor head_dim) dims of each q and
        k head turned at t ("rotate_half": dim j with j + r/2), base
        rope_theta (full) or swa_rope_theta (window); the rest as they are
    s_hj = q_h . k_g(h),j / sqrt(head_dim), g(h) = floor(h key heads /
        heads), j <= t (full) or t - sliding_window < j <= t (window)
    p_hj = exp(s_hj) / (exp(b_h) + sum_j exp(s_hj)) where the kind has a
        sink logit b_h (add_swa_attention_sink_bias,
        add_full_attention_sink_bias), else the plain softmax
    o_h = attention_value_scale sum_j p_hj v_g(h),j
    h <- h + concat(o_h) W_o
    y = rms(h; ffn_norm)
    moe_layer_freq 0: h <- h + W_down (silu(y W_gate) * (y W_up))
    moe_layer_freq 1: s = sigmoid(y W_r) over all router_experts in
        float32; the num_experts_per_tok largest of s + bias chosen
        (noaux_tc, n_group 1: no group limit), weighed s_e / sum of the
        chosen s (norm_topk_prob; routed_scaling_factor null: 1); h <- h
        + sum_e w_e E_e(y), each E a SwiGLU of moe_intermediate_size, the
        sum over the chosen experts in experts_held only (no shared
        expert)
    the kinds by the published index the layer stands for
        (published_layers): hybrid_layer_pattern 1 is a window layer
    after the last layer rms(h; final_norm) and the head.

``precision`` is ``reference/kimi_k2.py``'s and reaches the products,
q, k, v and the attention's weights; norms, rotation, softmax and the
router are float32. ``logits(..., margins=True)`` also returns each
position's least routing margin: how far a held expert's ``s + bias``
lies from the boundary of the choice.

What keeps a check inside a run's time: the two precisions a cell
compares share one compiled program a kind of layer (a traced flag
picks bfloat16 or HIGHEST), whose products with a weight are formed
from bfloat16 parts (``_matmul``: exact for bfloat16 weights, the
products HIGHEST forms); an expert reads only the tokens that chose it,
``Q_BLOCK`` of them a step (at ``float8`` an expert's inputs thus take
their scale from their step's tokens).

Weights: ``{"embed", "final_norm", "head", "layers": [layer, ...]}``; a
layer is ``{"attn_norm", "ffn_norm", "mixer": {"w_qkv", "wo"[, "sink"]},
"ffn": {...}}``, matrices ``(in, out)``.
"""

import functools
import json

import jax
import jax.numpy as jnp

from benchmark.lookup import load_module

_kimi = load_module("reference", "kimi_k2")
PRECISIONS = _kimi.PRECISIONS
HEAD_BLOCK = _kimi.HEAD_BLOCK
rms = _kimi.rms
Q_BLOCK = 128          # queries a block of the attention
ROW_BLOCKS = (2048, 1024, 512, 256, 128)
# a sequence past LONG positions runs padded to a multiple of LONG, so
# that the few lengths a cell checks share their compiled programs
LONG = 4096


def _parts(x, low):
    """``x`` as three bfloat16 parts whose sum is ``x`` to float32's
    precision, the last two zero where ``low``; one where ``x`` is
    bfloat16 already."""
    if x.dtype == jnp.bfloat16:
        return [x]
    parts, rest = [], x.astype(jnp.float32)
    for _ in range(3):
        part = rest.astype(jnp.bfloat16)
        parts.append(part)
        rest = jnp.where(low, 0.0, rest - part.astype(jnp.float32))
    return parts


def _round(x, precision):
    """``reference/kimi_k2.py``'s, where ``precision`` may also be a
    traced flag: ``bfloat16`` where it is true, ``highest`` where not."""
    if isinstance(precision, str):
        return _kimi._round(x, precision)
    return jnp.where(precision, _kimi._round(x, "bfloat16"),
                     x.astype(jnp.float32))


def _matmul(x, w, precision):
    """``x @ w``, float32 out: ``reference/kimi_k2.py``'s for a named
    precision. For the flag (``_round``) the products HIGHEST forms of
    the operands' bfloat16 parts (``_parts``: those whose orders add up
    to at most the third), with float32 accumulation: of bfloat16
    weights three products, of which at ``bfloat16`` the first alone is
    not zero."""
    if isinstance(precision, str):
        return _kimi._matmul(x, w, precision)
    xs, ws = _parts(x, precision), _parts(w, precision)
    out = [jnp.matmul(a, b, preferred_element_type=jnp.float32)
           for i, a in enumerate(xs) for j, b in enumerate(ws) if i + j < 3]
    first, *rest = out
    return first + sum(rest) if rest else first


def swiglu(x, w_gate, w_up, w_down, precision):
    return _matmul(jax.nn.silu(_matmul(x, w_gate, precision))
                   * _matmul(x, w_up, precision), w_down, precision)


def _by_rows(f, *xs):
    """``f`` over blocks of positions of ``xs`` (their leading axis, a
    multiple of ``Q_BLOCK``), the results put back in order."""
    t = xs[0].shape[0]
    block = next(b for b in ROW_BLOCKS if t % b == 0)
    out = jax.lax.map(lambda a: f(*a), tuple(
        x.reshape((t // block, block) + x.shape[1:]) for x in xs))
    return jax.tree_util.tree_map(
        lambda y: y.reshape((t,) + y.shape[2:]), out)


# -- layer kinds -------------------------------------------------------


def published_index(cfg, index):
    return cfg.get("published_layers", list(range(
        cfg["num_hidden_layers"])))[index]


def is_window(cfg, index):
    return cfg["hybrid_layer_pattern"][published_index(cfg, index)] == 1


def is_sparse(cfg, index):
    return cfg["moe_layer_freq"][published_index(cfg, index)] == 1


def widths(cfg, window):
    """``(heads, key heads, head_dim, v_head_dim)`` of the kind."""
    def get(key):
        return cfg.get("swa_" + key, cfg[key]) if window else cfg[key]
    return (get("num_attention_heads"), get("num_key_value_heads"),
            get("head_dim"), get("v_head_dim"))


def rotary_base(cfg, window):
    return cfg["swa_rope_theta"] if window else cfg["rope_theta"]


def rotated_dims(cfg, d):
    return int(cfg["partial_rotary_factor"] * d)


def value_scale(cfg):
    return cfg.get("attention_value_scale", 1.0)


def key_head(h, heads, key_heads):
    """The key head query head ``h`` reads."""
    return h * key_heads // heads


def sink_logit(mixer, cfg, window):
    """``(heads,)`` sink logits, or ``None`` where the kind has none."""
    kind = "swa" if window else "full"
    return mixer["sink"] if cfg.get(f"add_{kind}_attention_sink_bias") \
        else None


def visible(q_pos, k_pos, window):
    """``(queries, keys)``: which key each query sees."""
    seen = k_pos[None, :] <= q_pos[:, None]
    if window:
        seen &= k_pos[None, :] > q_pos[:, None] - window
    return seen


def turn(x, positions, theta, r):
    """``x``: ``(T, heads, d)``; the first ``r`` dims turned, "rotate_half"."""
    half = r // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:r]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle),
                            x[..., r:]], axis=-1)


# -- attention ---------------------------------------------------------


def attention(x, m, cfg, window, precision):
    """``x``: ``(T, hidden)`` normed, ``T`` a multiple of ``Q_BLOCK``.
    ``window``: whether the layer is a window layer."""
    heads, key_heads, d, d_v = widths(cfg, window)
    t, span = x.shape[0], cfg["sliding_window"] if window else 0
    r, theta = rotated_dims(cfg, d), rotary_base(cfg, window)
    qkv = _by_rows(lambda a: _matmul(a, m["w_qkv"], precision), x)
    positions = jnp.arange(t)
    q = turn(qkv[:, :heads * d].reshape(t, heads, d), positions, theta, r)
    k = turn(qkv[:, heads * d:(heads + key_heads) * d].reshape(
        t, key_heads, d), positions, theta, r)
    v = qkv[:, (heads + key_heads) * d:].reshape(t, key_heads, d_v)
    q, k, v = (_round(a, precision) for a in (q, k, v))
    # the query heads ordered by the key head they read, a group each
    order = sorted(range(heads), key=lambda h: key_head(h, heads, key_heads))
    assert all(key_head(h, heads, key_heads) == i * key_heads // heads
               for i, h in enumerate(order)), "unequal groups"
    q = q[:, jnp.asarray(order)].reshape(t, key_heads, heads // key_heads, d)
    back = jnp.argsort(jnp.asarray(order))
    sink = sink_logit(m, cfg, window)
    if sink is not None:
        sink = sink[jnp.asarray(order)].reshape(key_heads, -1)[..., None, None]
    if span:
        # a window block reads the span before it and itself
        k, v = (jnp.pad(a, ((span, 0), (0, 0), (0, 0))) for a in (k, v))

    def block(start):
        q_pos = start + jnp.arange(Q_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK)
        if span:
            kb = jax.lax.dynamic_slice_in_dim(k, start, span + Q_BLOCK)
            vb = jax.lax.dynamic_slice_in_dim(v, start, span + Q_BLOCK)
            k_pos = start - span + jnp.arange(span + Q_BLOCK)
        else:
            kb, vb, k_pos = k, v, positions
        scores = jnp.einsum("qgjd,kgd->gjqk", qb, kb) * d ** -0.5
        scores = jnp.where(visible(q_pos, k_pos, span) & (k_pos >= 0),
                           scores, -jnp.inf)
        top = jnp.max(scores, axis=-1, keepdims=True)
        if sink is not None:
            top = jnp.maximum(top, sink)
        e = jnp.exp(scores - top)
        total = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            total = total + jnp.exp(sink - top)
        o = jnp.einsum("gjqk,kgd->qgjd", _round(e / total, precision), vb)
        o = o.reshape(Q_BLOCK, heads, d_v)[:, back]
        return (value_scale(cfg) * o).reshape(Q_BLOCK, heads * d_v)

    o = jax.lax.map(block, jnp.arange(0, t, Q_BLOCK)).reshape(t, -1)
    return _by_rows(lambda a: _matmul(a, m["wo"], precision), o)


# -- feed-forward ------------------------------------------------------


def held_experts(cfg):
    count = cfg["n_routed_experts"]
    first, stop = cfg.get("experts_held", (0, count))
    return first, stop - first


def route_weights(scores, bias, chosen):
    """The chosen experts' weights: their scores (not the bias) over
    their sum."""
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    return picked / picked.sum(axis=1, keepdims=True)


def expert_layer(x, m, cfg, precision):
    """``(y, margin)``: the held experts' part of the layer, and each
    position's routing margin."""
    top_k = cfg["num_experts_per_tok"]
    bias = m["router_bias"].astype(jnp.float32)
    scores = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                       m["router"].astype(jnp.float32)))
    ranked, chosen = jax.lax.top_k(scores + bias, top_k + 1)
    chosen = chosen[:, :top_k]
    weights = (cfg.get("routed_scaling_factor") or 1.0) * route_weights(
        scores, bias, chosen)
    first, count = held_experts(cfg)

    def add_expert(e, y):
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0),
                       axis=1)
        # the tokens that chose the expert first, Q_BLOCK of them a step
        # (a step's tail of tokens that did not choose it weighs 0, and
        # the index past the last position reads zeros and writes nothing)
        order = jnp.pad(jnp.argsort(mine == 0.0, stable=True),
                        (0, -x.shape[0] % Q_BLOCK), constant_values=x.shape[0])
        steps = -(-jnp.sum(mine != 0.0) // Q_BLOCK)
        w = [jnp.asarray(m[name])[e]
             for name in ("experts_gate", "experts_up", "experts_down")]

        def step(i, y):
            at = jax.lax.dynamic_slice_in_dim(order, i * Q_BLOCK, Q_BLOCK)
            x_at, mine_at = (jnp.take(a, at, axis=0, mode="fill", fill_value=0)
                             for a in (x, mine))
            return y.at[at].add(mine_at[:, None] * swiglu(x_at, *w, precision),
                                mode="drop")

        return jax.lax.fori_loop(0, steps, step, y)

    y = jax.lax.fori_loop(0, count, add_expert, jnp.zeros_like(x))
    # a held expert's distance to the boundary of the choice: chosen,
    # above the (k+1)-th largest of s + bias; not chosen, below the k-th
    mine = (scores + bias)[:, first:first + count]
    last_in, first_out = ranked[:, top_k - 1:top_k], ranked[:, top_k:]
    margin = jnp.where(mine >= last_in, mine - first_out, last_in - mine)
    return y, margin.min(axis=1)


# -- the model ---------------------------------------------------------


def layer_forward(h, layer, cfg, window, sparse, precision):
    """One layer over one sequence. ``h``: ``(T, hidden)`` float32.
    Returns ``(h, margin)``; ``margin`` is ``inf`` for a dense layer."""
    eps = cfg["layernorm_epsilon"]
    h = h + attention(_by_rows(lambda a: rms(a, layer["attn_norm"], eps), h),
                      layer["mixer"], cfg, window, precision)

    def ffn(u):
        y, f = rms(u, layer["ffn_norm"], eps), layer["ffn"]
        if sparse:
            return expert_layer(y, f, cfg, precision)
        return (swiglu(y, f["w_gate"], f["w_up"], f["w_down"], precision),
                jnp.full((u.shape[0],), jnp.inf, jnp.float32))

    y, margin = _by_rows(ffn, h)
    return h + y, margin


@functools.partial(jax.jit, static_argnames=("sizes", "window", "sparse",
                                             "precision"))
def _layer(h, margin, layer, low, sizes, window, sparse, precision):
    # a compiled program a kind of layer and a length, which the two
    # precisions the cell compares share (``low`` picks), and one for
    # each lower precision: a layer at a time, so that only one layer's
    # float32 copy of its weights is alive at a time
    with jax.default_matmul_precision("highest"):
        h, layer_margin = layer_forward(h, layer, json.loads(sizes), window,
                                        sparse, low if precision is None
                                        else precision)
    return h, jnp.minimum(margin, layer_margin)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(h, margin, positions, final_norm, head, low, eps, precision):
    precision = low if precision is None else precision
    x = rms(h[positions], final_norm, eps)
    with jax.default_matmul_precision("highest"):
        out = jnp.concatenate(
            [_matmul(x, head[:, s:s + HEAD_BLOCK], precision)
             for s in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)
    return out, margin[positions]


def logits(weights, ids, cfg, precision="highest", positions=None,
           margins=False):
    """``ids``: ``(T,)`` one sequence, or ``(n, T)``. Every position's
    logits, float32 ``(..., T, vocab)`` (``positions``: only those);
    with ``margins``, ``(logits, least routing margin a position)``.
    The sequence runs padded to a multiple of ``Q_BLOCK``, or of
    ``LONG`` past it: the model is causal, so what follows the last id
    moves nothing before it."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    ids = jnp.asarray(ids)
    if ids.ndim == 2:
        rows = [logits(weights, row, cfg, precision, positions, margins)
                for row in ids]
        if margins:
            return tuple(jnp.stack(part) for part in zip(*rows))
        return jnp.stack(rows)
    t = len(ids)
    ids = jnp.pad(ids, (0, -t % (LONG if t > LONG else Q_BLOCK)))
    low = jnp.asarray(precision == "bfloat16")
    named = None if precision in ("highest", "bfloat16") else precision
    sizes = json.dumps(cfg, sort_keys=True)
    h = jnp.take(weights["embed"], ids, axis=0).astype(jnp.float32)
    margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
    for index, layer in enumerate(weights["layers"]):
        h, margin = _layer(h, margin, layer, low, sizes, is_window(cfg, index),
                           is_sparse(cfg, index), named)
    out, margin = _head(
        h, margin, jnp.arange(t) if positions is None else
        jnp.asarray(positions), weights["final_norm"], weights["head"], low,
        cfg["layernorm_epsilon"], named)
    return (out, margin) if margins else out
