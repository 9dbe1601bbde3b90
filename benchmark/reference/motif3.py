"""Plain Motif-3 decoder: grouped differential attention on a latent
cache (GDLA) in every layer, over a window in some, PolyNorm MLPs and
sigmoid-routed experts with a shared one, inside a residual path of
``n = mhc_expansion_rate`` streams a token (mHC, as
``reference/xing4_0.py`` writes it, whose functions this file uses).
``jax.numpy`` in float32 over one whole sequence under
``jax.default_matmul_precision("highest")``: no state, no chunks, no
cache, no ring, no kernels, no code of the program. The attention is
taken a block of ``Q_BLOCK`` queries at a time against the whole
sequence, and every other part a block of positions at a time, so
that one row of 16k positions fits beside the model; a window is a
mask over the whole sequence.

One layer, for a token's streams ``X`` at position ``t`` (sizes from
the model's ``config.json``):

    around each sub-layer F: X' = H_res X + H_post^T F(rms(H_pre X)),
        the coefficients as in reference/xing4_0.py (hc_eps 1e-6, the
        clamp +-30 before the exp: xing4_0's, which Motif's config does
        not give), then every stream clipped to +-hidden_clamp
    attention (GDLA), x = rms(u; attn_norm):
        c_q = rms(x W_dq; q_norm);  q = c_q W_uq: heads x [q_n; q_r]
        [c'; k'] = x W_dkv;  c = rms(c'; kv_norm);  k_r = rope(k', t)
        key head g of num_key_value_heads: k_g = [c W_uk[g]; k_r],
            v_g = c W_uv[g]
        the query heads in groups of heads / groups consecutive ones,
            the first of a group its noise head, the others signal heads
        A_h = softmax_{s in M(t)}(q_h . k_g(s) / sqrt(head_dim))
        M(t) = {s <= t}; in a window layer {t - sliding_window < s <= t}
        signal head h of group g: o_h = (A_h - lambda_{t,h} A_noise(g)) v_g
        lambda_{t,h} = sigmoid(x W_lambda)[h]
        out = (concat(o_h) * sigmoid(x W_gate)) W_o
    a layer under max_window_layers is full; from there on, by the
        published index i the cut's layer stands for (published_layers),
        i + 1 a multiple of sliding_window_period is full, the others
        window layers
    feed-forward, y = rms(u; ffn_norm), an MLP: W_down (P(y W_gate) * y W_up)
        P(v) = s (w0 n(v^3) + w1 n(v^2) + w2 n(v) + clip(b, +-c)),
        n(z) = z / sqrt(mean(z^2) + rms_norm_eps) over the MLP's width,
        s = polynorm_output_scale, c = polynorm_bias_clamp, (w, b) the
        MLP's own
    layers under n_dense_first_layers: the dense MLP; the others:
        scores sigmoid(y W_r) over all router_experts, float32; the
        experts_top_k largest chosen (no selection bias), weighed
        route_scale s_e / sum of the chosen s; the sum over the chosen
        experts in experts_held only, plus the shared expert
    after the last layer the streams summed, rms(final_norm), the head.

``rope``: YaRN frequencies (``rope_scaling``), element ``2 j`` with
``2 j + 1`` (``reference/kimi_k2.py``'s); the softmax scale carries no
YaRN factor (``apply_yarn_scaling`` false). ``precision`` is
``reference/kimi_k2.py``'s and reaches the products, the latent and
rotated key, q, k, v and the maps; norms, rotation, softmax, the router
and the residual path are float32. ``logits(..., margins=True)`` also
returns each position's least routing margin, as there.

Weights: ``{"embed", "final_norm", "head", "layers": [layer, ...]}``; a
layer is ``{"attn_norm", "ffn_norm", "mixer_hc", "ffn_hc", "mixer":
{...}, "ffn": {...}}`` with the names used below, matrices ``(in,
out)``.
"""

import functools
import json

import jax
import jax.numpy as jnp

from benchmark.lookup import load_module

_kimi = load_module("reference", "kimi_k2")
_xing = load_module("reference", "xing4_0")
PRECISIONS = _kimi.PRECISIONS
HEAD_BLOCK = _kimi.HEAD_BLOCK
rms, rotary = _kimi.rms, _kimi.rotary
_round, _matmul = _kimi._round, _kimi._matmul
HC_EPS, HC_CLAMP = 1e-6, 30.0
Q_BLOCK = 128          # queries a block of the attention
ROW_BLOCKS = (2048, 1024, 512, 256, 128)


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
    if not cfg.get("use_sliding_window"):
        return False
    i = published_index(cfg, index)
    return (i >= cfg["max_window_layers"]
            and (i + 1) % cfg["sliding_window_period"] != 0)


def visible(q_pos, k_pos, window):
    """``(queries, keys)``: which key each query sees."""
    seen = k_pos[None, :] <= q_pos[:, None]
    if window:
        seen &= k_pos[None, :] > q_pos[:, None] - window
    return seen


# -- attention ---------------------------------------------------------


def difference(p, lam):
    """``p``: ``(groups, per, Q, T)`` the maps of a group's query heads,
    the first its noise head; ``lam`` ``(Q, groups, per - 1)``. The
    signal heads' maps less lambda times their group's noise map."""
    return p[:, 1:] - jnp.transpose(lam, (1, 2, 0))[..., None] * p[:, :1]


def gdla_mixer(x, m, cfg, window, precision):
    """``x``: ``(T, hidden)`` normed. ``window``: the positions a query
    sees, or 0 for all before it."""
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per, rope = heads // groups, cfg["qk_rope_head_dim"]
    nope, rank = cfg["head_dim"] - rope, cfg["kv_lora_rank"]
    d_v, eps, t = cfg["v_head_dim"], cfg["rms_norm_eps"], x.shape[0]
    freq = _kimi.yarn_frequencies(rope, cfg["rope_theta"],
                                  cfg.get("rope_scaling") or {})
    scale = cfg["head_dim"] ** -0.5

    c_q = rms(_matmul(x, m["w_dq"], precision), m["q_norm"], eps)
    down = _matmul(x, m["w_dkv"], precision)
    c_kv = _round(rms(down[:, :rank], m["kv_norm"], eps), precision)
    k_r = _round(rotary(down[:, rank:], freq), precision)
    kv = _matmul(c_kv, m["w_ukv"].reshape(rank, groups * (nope + d_v)),
                 precision).reshape(t, groups, nope + d_v)
    k = _round(jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r[:, None, :], (t, groups, rope))], axis=-1), precision)
    v = _round(kv[..., nope:], precision)
    k_pos = jnp.arange(t)

    def block(start):
        rows = jax.lax.dynamic_slice_in_dim
        xb, cb = rows(x, start, Q_BLOCK), rows(c_q, start, Q_BLOCK)
        q = _matmul(cb, m["w_uq"], precision).reshape(Q_BLOCK, heads,
                                                      nope + rope)
        q_pos = start + jnp.arange(Q_BLOCK)
        # rotary at the block's own positions
        angle = q_pos.astype(jnp.float32)[:, None, None] * freq
        pairs = q[..., nope:].reshape(Q_BLOCK, heads, rope // 2, 2)
        a, b = pairs[..., 0], pairs[..., 1]
        q_r = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                         b * jnp.cos(angle) + a * jnp.sin(angle)],
                        axis=-1).reshape(Q_BLOCK, heads, rope)
        q = _round(jnp.concatenate([q[..., :nope], q_r], axis=-1),
                   precision).reshape(Q_BLOCK, groups, per, nope + rope)
        scores = jnp.einsum("qgjd,kgd->gjqk", q, k) * scale
        seen = visible(q_pos, k_pos, window)
        p = _round(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                                  axis=-1), precision)
        lam = jax.nn.sigmoid(_matmul(xb, m["w_lambda"], precision)).reshape(
            Q_BLOCK, groups, per - 1)
        o = jnp.einsum("gjqk,kgd->qgjd", difference(p, lam), v).reshape(
            Q_BLOCK, -1)
        if cfg.get("elementwise_attn_output_gate"):
            o = o * jax.nn.sigmoid(_matmul(xb, m["w_gate"], precision))
        return _matmul(o, m["wo"], precision)

    out = jax.lax.map(block, jnp.arange(0, t, Q_BLOCK))
    return out.reshape(t, -1)


# -- feed-forward ------------------------------------------------------


def poly_norm(v, weight, bias, cfg):
    w = weight.astype(jnp.float32)
    b = jnp.clip(bias.astype(jnp.float32), -cfg["polynorm_bias_clamp"],
                 cfg["polynorm_bias_clamp"])

    def n(z):
        return z / jnp.sqrt(jnp.mean(z * z, axis=-1, keepdims=True)
                            + cfg["rms_norm_eps"])

    return cfg["polynorm_output_scale"] * (
        w[0] * n(v ** 3) + w[1] * n(v ** 2) + w[2] * n(v) + b[0])


def mlp(x, gate, up, down, weight, bias, cfg, precision):
    return _matmul(poly_norm(_matmul(x, gate, precision), weight, bias, cfg)
                   * _matmul(x, up, precision), down, precision)


def held_experts(cfg):
    count = cfg["num_experts"]
    first, stop = cfg.get("experts_held", (0, count))
    return first, stop - first


def expert_layer(x, f, cfg, precision):
    """``(y, margin)``: the held experts' and the shared expert's part
    of the layer, and each position's routing margin."""
    top_k = cfg["experts_top_k"]
    scores = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                       f["router"].astype(jnp.float32)))
    ranked, chosen = jax.lax.top_k(scores, top_k + 1)
    chosen = chosen[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    weights = picked / picked.sum(axis=1, keepdims=True) \
        if cfg.get("route_norm", True) else picked
    weights = cfg["route_scale"] * weights
    first, count = held_experts(cfg)
    y = mlp(x, f["shared_gate"], f["shared_up"], f["shared_down"],
            f["shared_poly_weight"], f["shared_poly_bias"], cfg, precision)

    def add_expert(e, y):                   # every token through every
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0),
                       axis=1)              # held expert, then the mask
        return y + mine[:, None] * mlp(x, *(
            jnp.asarray(f[name])[e] for name in (
                "experts_gate", "experts_up", "experts_down",
                "experts_poly_weight", "experts_poly_bias")), cfg, precision)

    y = jax.lax.fori_loop(0, count, add_expert, y)
    mine = scores[:, first:first + count]
    last_in, first_out = ranked[:, top_k - 1:top_k], ranked[:, top_k:]
    margin = jnp.where(mine >= last_in, mine - first_out, last_in - mine)
    return y, margin.min(axis=1)


# -- the model ---------------------------------------------------------


def _hc_cfg(cfg):
    """What ``reference/xing4_0.py``'s coefficients read, from Motif's
    keys and the path's assumed epsilon and clamp."""
    return {"rms_norm_eps": cfg["rms_norm_eps"], "hc_eps": HC_EPS,
            "hc_sinkhorn_iters": cfg["mhc_sinkhorn_iters"],
            "mhc_h_res_clamp_min": -HC_CLAMP, "mhc_h_res_clamp_max": HC_CLAMP}


def around(x, hc, cfg, sub):
    """``X' = clip(H_res X + H_post^T sub(H_pre X))``, ``sub`` over the
    whole sequence, returning ``(y, margin)``."""
    def read(x):
        h_pre, h_post, h_res = _xing.coefficients(x, hc, _hc_cfg(cfg))
        return jnp.einsum("tn,tnc->tc", h_pre, x), h_post, h_res

    u, h_post, h_res = _by_rows(read, x)
    y, margin = sub(u)
    bound = cfg["hidden_clamp"]

    def write(x, y, h_post, h_res):
        return jnp.clip(jnp.einsum("tij,tjc->tic", h_res, x)
                        + h_post[:, :, None] * y[:, None, :], -bound, bound)

    return _by_rows(write, x, y, h_post, h_res), margin


def layer_forward(x, layer, cfg, window, sparse, precision):
    """One layer over one sequence. ``x``: ``(T, n, C)`` float32;
    ``window``: the positions a query sees, 0 for a full layer.
    Returns ``(x, margin)``; ``margin`` is ``inf`` for a dense layer."""
    eps, t = cfg["rms_norm_eps"], x.shape[0]
    no_margin = jnp.full((t,), jnp.inf, jnp.float32)

    def mixer(u):
        return gdla_mixer(rms(u, layer["attn_norm"], eps), layer["mixer"],
                          cfg, window, precision), no_margin

    def ffn(u):
        f = layer["ffn"]

        def rows(u):
            y = rms(u, layer["ffn_norm"], eps)
            if sparse:
                return expert_layer(y, f, cfg, precision)
            return mlp(y, f["w_gate"], f["w_up"], f["w_down"],
                       f["poly_weight"], f["poly_bias"], cfg,
                       precision), jnp.full((u.shape[0],), jnp.inf)

        return _by_rows(rows, u)

    x, _ = around(x, layer["mixer_hc"], cfg, mixer)
    return around(x, layer["ffn_hc"], cfg, ffn)


@functools.partial(jax.jit, static_argnames=("sizes", "window", "sparse",
                                             "precision"))
def _layer(x, layer, sizes, window, sparse, precision):
    # one compiled program a kind of layer, so that only one layer's
    # float32 copy of its weights is alive at a time
    with jax.default_matmul_precision("highest"):
        return layer_forward(x, layer, json.loads(sizes), window, sparse,
                             precision)


def hidden(weights, ids, cfg, precision="highest"):
    """``(final-norm output, least routing margin)`` at every position
    of one sequence (run padded to a multiple of ``Q_BLOCK``: the model
    is causal, so what follows the last id moves nothing before it)."""
    t = len(ids)
    ids = jnp.pad(jnp.asarray(ids), (0, -t % Q_BLOCK))
    h = jnp.take(weights["embed"], ids, axis=0).astype(jnp.float32)
    x = _xing.copy_in(h, cfg["mhc_expansion_rate"])
    sizes = json.dumps(cfg, sort_keys=True)
    margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
    for index, layer in enumerate(weights["layers"]):
        x, layer_margin = _layer(
            x, layer, sizes,
            cfg["sliding_window"] if is_window(cfg, index) else 0,
            index >= cfg["n_dense_first_layers"], precision)
        margin = jnp.minimum(margin, layer_margin)
    return rms(_xing.read_out(x), weights["final_norm"],
               cfg["rms_norm_eps"])[:t], margin[:t]


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
