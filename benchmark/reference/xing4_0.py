"""Plain Xing4.0 decoder: the DeepSeek-V3 layer (latent attention, a
dense SwiGLU or sigmoid-routed experts with a shared one, as
``reference/kimi_k2.py`` writes them, whose mixer and expert layer this
file uses) inside a residual path of ``n = hc_mult`` streams a token:
manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606). ``jax.numpy`` in float32 over one
whole sequence under ``jax.default_matmul_precision("highest")``: no
state, no chunks, no cache, no kernels, no code of the program.

A token's stream is ``X`` in ``R^{n x C}``, ``C = hidden_size``. Into
the first layer the embedding is copied to all ``n`` rows of ``X``.
Around each of a layer's two sub-layers ``F`` (the latent mixer; the
dense SwiGLU under ``first_k_dense_replace``, else the experts), with
the sub-layer's own ``phi`` ``(n C, 2 n + n^2)``, ``alpha`` (3),
``b_pre`` (n), ``b_post`` (n) and ``b_res`` (n, n):

    x^ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)  (no learned scale)
    [p | q | r] = x^ phi
    H_pre = sigmoid(alpha_0 p + b_pre)
    H_post = 2 sigmoid(alpha_1 q + b_post)
    M = exp(clip(alpha_2 mat(r) + b_res, mhc_h_res_clamp_min,
                 mhc_h_res_clamp_max)),  mat row-major
    H_res = hc_sinkhorn_iters rounds on M of: every column over (its
            sum + hc_eps), then every row over (its sum + hc_eps)
    u = H_pre X;  y = F(rms(u; the sub-layer's norm))
    X' = H_res X + H_post^T y

After the last layer the rows of ``X`` are summed, then
``rms(.; final_norm)`` and the head. The copy in, the sum out, ``hc_eps``
as the divisor's epsilon, the clamp before the ``exp`` and the un-scaled
norm are this repository's reading of the config's keys
(``configs/xing4_0_29b_a4b.json`` lists them under ``assumed``).

``precision`` is ``reference/kimi_k2.py``'s (``highest``, ``bfloat16``,
``float8_weights``, ``float8``) and reaches the sub-layers alone: the
residual path is float32 at highest precision in each, as the
configuration states it. ``logits(..., margins=True)`` also returns each
position's least routing margin, as there.

Weights: ``reference/kimi_k2.py``'s, and in each layer ``"mixer_hc"``
and ``"ffn_hc"``: ``{"phi", "alpha", "b_pre", "b_post", "b_res"}``.
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
# a sequence runs padded to a multiple of this many positions (the
# model is causal: what follows the last id moves nothing before it),
# so that five lengths compile and not every one a seed's prompts come
# to: a run's check spent 60 s compiling for a length of its own. 416
# divides 2,080 = 2,048 + 32, the longest sequence of the cell
# ``xing4_0_29b_a4b.extract``, which therefore runs unpadded
PAD_TO = 416


# -- the residual path -------------------------------------------------


def copy_in(h, n):
    """``(T, C)`` -> ``(T, n, C)``: the embedding in every stream."""
    return jnp.repeat(h[:, None, :], n, axis=1)


def read_out(x):
    """``(T, n, C)`` -> ``(T, C)``: the streams summed."""
    return x.sum(axis=1)


def stream_norm(flat, eps):
    """RMSNorm over all ``n C`` values of a token, no learned scale."""
    return flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + eps)


def post_gate(z):
    return 2.0 * jax.nn.sigmoid(z)


def sinkhorn(m, iters, eps):
    """``m``: ``(T, n, n)`` positive, ``m[t, i, j]`` row ``i`` column
    ``j``. Columns first, then rows: the paper's ``T_r(T_c(.))``."""
    for _ in range(iters):
        m = m / (m.sum(axis=1, keepdims=True) + eps)    # a column's sum
        m = m / (m.sum(axis=2, keepdims=True) + eps)    # a row's sum
    return m


def coefficients(x, hc, cfg):
    """``(H_pre (T, n), H_post (T, n), H_res (T, n, n))``."""
    t, n, _ = x.shape
    x_hat = stream_norm(x.reshape(t, -1).astype(jnp.float32),
                        cfg["rms_norm_eps"])
    out = jnp.matmul(x_hat, hc["phi"].astype(jnp.float32))
    alpha = hc["alpha"].astype(jnp.float32)
    p, q, r = out[:, :n], out[:, n:2 * n], out[:, 2 * n:].reshape(t, n, n)
    h_pre = jax.nn.sigmoid(alpha[0] * p + hc["b_pre"].astype(jnp.float32))
    h_post = post_gate(alpha[1] * q + hc["b_post"].astype(jnp.float32))
    m = jnp.exp(jnp.clip(
        alpha[2] * r + hc["b_res"].astype(jnp.float32),
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    return h_pre, h_post, sinkhorn(m, cfg["hc_sinkhorn_iters"],
                                   cfg["hc_eps"])


def around(x, hc, cfg, sub):
    """``X' = H_res X + H_post^T sub(H_pre X)``; ``sub`` returns ``(y,
    margin)``."""
    h_pre, h_post, h_res = coefficients(x, hc, cfg)
    y, margin = sub(jnp.einsum("tn,tnc->tc", h_pre, x))
    return (jnp.einsum("tij,tjc->tic", h_res, x)
            + h_post[:, :, None] * y[:, None, :]), margin


# -- the model ---------------------------------------------------------


def layer_forward(x, layer, cfg, sparse, precision):
    """One layer over one sequence. ``x``: ``(T, n, C)`` float32.
    Returns ``(x, margin)``; ``margin`` is ``inf`` for a dense layer."""
    eps = cfg["rms_norm_eps"]
    no_margin = jnp.full((x.shape[0],), jnp.inf, jnp.float32)

    def mixer(u):
        return _kimi.latent_mixer(rms(u, layer["attn_norm"], eps),
                                  layer["mixer"], cfg, precision), no_margin

    def ffn(u):
        y = rms(u, layer["ffn_norm"], eps)
        if sparse:
            return _kimi.expert_layer(y, layer["ffn"], cfg, precision)
        f = layer["ffn"]
        return _kimi.swiglu(y, f["w_gate"], f["w_up"], f["w_down"],
                            precision), no_margin

    x, _ = around(x, layer["mixer_hc"], cfg, mixer)
    return around(x, layer["ffn_hc"], cfg, ffn)


@functools.partial(jax.jit, static_argnames=("sizes", "sparse", "precision"))
def _layer(x, layer, sizes, sparse, precision):
    # one compiled program a kind of layer, so that only one layer's
    # float32 copy of its weights is alive at a time
    with jax.default_matmul_precision("highest"):
        return layer_forward(x, layer, json.loads(sizes), sparse, precision)


def hidden(weights, ids, cfg, precision="highest"):
    """``(final-norm output, least routing margin)`` at every position
    of one sequence."""
    t = len(ids)
    ids = jnp.pad(jnp.asarray(ids), (0, -t % PAD_TO))
    h = jnp.take(weights["embed"], ids, axis=0).astype(jnp.float32)
    x = copy_in(h, cfg["hc_mult"])
    sizes = json.dumps(cfg, sort_keys=True)
    margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
    for index, layer in enumerate(weights["layers"]):
        x, layer_margin = _layer(x, layer, sizes,
                                 index >= cfg["first_k_dense_replace"],
                                 precision)
        margin = jnp.minimum(margin, layer_margin)
    return rms(read_out(x), weights["final_norm"],
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
        blocks = [_kimi._matmul(x, head[:, s:s + HEAD_BLOCK], precision)
                  for s in range(0, head.shape[1], HEAD_BLOCK)]
    out = jnp.concatenate(blocks, axis=-1)
    return (out, margin) if margins else out
