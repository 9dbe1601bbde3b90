"""Operations and bytes a decoder of grouped differential attention on a
latent cache (GDLA) needs, its layers full or over a window, over a
PolyNorm MLP or sparse experts, inside a residual path of several
streams (``model_type`` ``Motif``), from the sizes in the model's
``config.json`` (Motif's own keys) and from what the program counted:
the (token, expert) pairs it served, the tokens round the residual
path's sub-layers (``hc_sublayer_tokens``) and the cached positions the
window layers' attention read (``window_positions``).

As in ``opcount_latent_lm.py`` these are the yardstick's counts: what
the mathematics requires, not what an implementation executes. Every
query head attends, noise heads as signal heads; a prompt's (query,
key) pairs are counted in the expanded form (``2 head_dim + 2 d_v`` a
pair and query head) and a decode step's cached positions in the
absorbed form (``2 (rank + rope) + 2 rank`` a position and query head).
A full layer's query at position ``p`` reads ``p + 1`` positions, a
window layer's ``min(p + 1, sliding_window)``: past the window a
window layer's work a token does not grow with the context. Padded
positions, the key blocks a window prefill reads beyond a query's
window, re-expanded cache blocks and lane padding are the
implementation's and are NOT counted, nor is element-wise work
(PolyNorm's powers and norms, the difference of the maps, the gates'
sigmoids). Weights are two bytes a parameter (the configuration's
bfloat16) and are read once a step, an expert only where a pair
touches it; a cached position is two bytes a value and is read once a
decode step, a full layer's cache to the context, a window layer's
ring to its window.
"""

from __future__ import annotations

from typing import Mapping, Tuple

from benchmark import opcount_hc_lm
from benchmark.opcount_hybrid_lm import (  # noqa: F401
    WEIGHT_BYTES, attended_pairs, cache_entry_bytes, dense_params,
    expert_params, experts_touched, head_params)


def held(cfg: Mapping) -> int:
    first, stop = cfg.get("experts_held", (0, cfg["num_experts"]))
    return stop - first


def router_params(cfg: Mapping) -> int:
    """The router over every published expert: 1,572,864."""
    return cfg["hidden_size"] * cfg.get("router_experts", cfg["num_experts"])


def layer_kinds(cfg: Mapping):
    """``[(window, sparse), ...]`` a layer: ``window`` the positions its
    attention sees (0: all before it), by the published index the layer
    stands for (``published_layers``); ``sparse`` whether it is an
    expert layer."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        p = cfg.get("published_layers", range(cfg["num_hidden_layers"]))[i]
        window = (cfg.get("use_sliding_window")
                  and p >= cfg["max_window_layers"]
                  and (p + 1) % cfg["sliding_window_period"] != 0)
        out.append((cfg["sliding_window"] if window else 0,
                    i >= cfg["n_dense_first_layers"]))
    return out


def counts(cfg: Mapping) -> Tuple[int, int, int, int]:
    """``(full, window, dense, expert)`` layers. (2, 3, 1, 4) for the
    cut."""
    kinds = layer_kinds(cfg)
    window = sum(w > 0 for w, _ in kinds)
    sparse = sum(s for _, s in kinds)
    return len(kinds) - window, window, len(kinds) - sparse, sparse


def mixer_params(cfg: Mapping) -> int:
    """A GDLA mixer's matrices: the queries' two, the latent's down and
    up projections (key heads), lambda a signal head, the gate a signal
    head channel and the output. 91,750,400 as published."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    signal = heads - cfg["num_noise_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, d_v = cfg["head_dim"] - rope, cfg["v_head_dim"]
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * cfg[
        "head_dim"] + h * (rank + rope)
        + rank * cfg["num_key_value_heads"] * (nope + d_v)
        + h * signal + 2 * h * signal * d_v)


def _hc(cfg: Mapping) -> dict:
    """The keys ``opcount_hc_lm`` reads, from Motif's."""
    return {"hc_mult": cfg["mhc_expansion_rate"],
            "hidden_size": cfg["hidden_size"],
            "hc_sinkhorn_iters": cfg["mhc_sinkhorn_iters"]}


def shared_params(cfg: Mapping) -> int:
    return cfg.get("num_shared_experts", 1) * expert_params(cfg)


def vector_params(cfg: Mapping) -> int:
    """Leaves that are no matrix: two norms a layer and the mixer's
    two, PolyNorm's four an MLP (each expert's, the shared one's), the
    final norm."""
    full, window, dense, sparse = counts(cfg)
    mlps = dense + sparse * (held(cfg) + 1)
    return (cfg["num_hidden_layers"] * (
        2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"])
        + 4 * mlps + cfg["hidden_size"])


def parameters(cfg: Mapping) -> int:
    """Every leaf of the model as this chip holds it. 3,928.5M for the
    cut of one dense and four expert layers."""
    full, window, dense, sparse = counts(cfg)
    layers = cfg["num_hidden_layers"]
    return (layers * mixer_params(cfg) + dense * dense_params(cfg)
            + sparse * (router_params(cfg) + shared_params(cfg)
                        + held(cfg) * expert_params(cfg))
            + 2 * head_params(cfg) + vector_params(cfg)
            + 2 * layers * opcount_hc_lm.hc_params(_hc(cfg)))


def pair_flops(cfg: Mapping) -> float:
    """One (query, key) pair over every query head, expanded form:
    51,200 as published."""
    return cfg["num_attention_heads"] * 2.0 * (cfg["head_dim"]
                                               + cfg["v_head_dim"])


def position_flops(cfg: Mapping) -> float:
    """One cached position against one token's query heads, absorbed
    form: 174,080 as published."""
    return cfg["num_attention_heads"] * 2.0 * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def token_flops(cfg: Mapping) -> float:
    """One token through every layer, without the routed experts, the
    attention's pairs and the residual path: two operations a matrix
    parameter."""
    full, window, dense, sparse = counts(cfg)
    return 2.0 * (cfg["num_hidden_layers"] * mixer_params(cfg)
                  + dense * dense_params(cfg)
                  + sparse * (router_params(cfg) + shared_params(cfg)))


def window_read(tokens: float, window: int) -> float:
    """Positions a window layer's attention reads for a row of
    ``tokens`` tokens: position ``p`` reads ``min(p + 1, window)``."""
    if tokens <= window:
        return tokens * (tokens + 1) / 2.0
    return window * (window + 1) / 2.0 + (tokens - window) * window


def decode_window_positions(cfg: Mapping, rows: int, steps: int) -> float:
    """What the decode steps of a call read in the window layers, rows
    whose prompts are a window long or more: a window a step."""
    _, window, _, _ = counts(cfg)
    return window * rows * steps * cfg["sliding_window"]


def window_attention(cfg: Mapping, positions: float,
                     decode_positions: float = 0.0) -> Tuple[float, float]:
    """``(flops, bytes)`` the window layers' attention needs for
    ``positions`` (query, cached position) reads (the program's
    ``window_positions``) of which ``decode_positions`` fell in decode
    steps: each at the expanded form's operations, the least of the two
    forms, and a decode step's read once from the ring (a prefill's
    keys are the stretch's own or the ring's, read once a block of
    queries: left out). Past the window it is the same a token whatever
    the prompt's length."""
    return (positions * pair_flops(cfg),
            decode_positions * float(cache_entry_bytes(cfg)))


def resident_params(cfg: Mapping) -> int:
    """Parameters every step reads whatever is routed (the residual
    path's leaves are ``opcount_hc_lm``'s, counted apart)."""
    full, window, dense, sparse = counts(cfg)
    return (cfg["num_hidden_layers"] * mixer_params(cfg)
            + dense * dense_params(cfg)
            + sparse * (router_params(cfg) + shared_params(cfg))
            + head_params(cfg))


def _path(cfg, sublayer_tokens):
    return opcount_hc_lm.hc_path(_hc(cfg), sublayer_tokens)


def _path_params(cfg):
    return (2 * cfg["num_hidden_layers"]
            * opcount_hc_lm.hc_params(_hc(cfg)))


def model_flops(cfg: Mapping, prompt_tokens: int, new_tokens: int,
                rows: int, expert_pairs: int, sublayer_tokens: float,
                window_positions: float) -> float:
    """A batch generation: every prompt token and every new token but
    each row's last through the layers and round the residual path,
    ``expert_pairs`` pairs through an expert, the head once a row for
    the prompt and once for every new token but the last; a full
    layer's prompt pairs in the expanded form (at the rows' mean
    length: no more than the true sum) and its decode steps' positions
    in the absorbed form; a window layer's reads as counted, its
    prompt's in the expanded form and its decode steps' in the
    absorbed."""
    full, _, _, _ = counts(cfg)
    through = prompt_tokens + new_tokens - rows
    steps = new_tokens // max(rows, 1) - 1
    decode_window = min(decode_window_positions(cfg, rows, steps),
                        window_positions)
    full_decode = steps * prompt_tokens + rows * steps * (steps + 1) / 2.0
    return (through * token_flops(cfg)
            + expert_pairs * 2.0 * expert_params(cfg)
            + _path(cfg, sublayer_tokens)[0]
            + full * rows * attended_pairs(prompt_tokens / max(rows, 1), 1)
            * pair_flops(cfg)
            + full * full_decode * position_flops(cfg)
            + (window_positions - decode_window) * pair_flops(cfg)
            + decode_window * position_flops(cfg)
            + new_tokens * 2.0 * head_params(cfg))


def decode_step(cfg: Mapping, rows: int, context: float,
                pairs_a_token: float) -> Tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences whose
    caches hold ``context`` positions each; ``pairs_a_token``: pairs an
    expert layer serves a token (counted). Resident weights, the path's
    leaves and each touched expert read once; a full layer's cache to
    the context, a window layer's ring to its window."""
    full, window, _, sparse = counts(cfg)
    width = min(context, cfg["sliding_window"])
    pairs = rows * pairs_a_token                    # a layer a step
    path = _path(cfg, rows * 2 * cfg["num_hidden_layers"])
    flops = (rows * (token_flops(cfg) + 2.0 * head_params(cfg))
             + sparse * pairs * 2.0 * expert_params(cfg)
             + (full * context + window * width) * rows * position_flops(cfg)
             + path[0])
    weights = (resident_params(cfg) + _path_params(cfg) + sparse
               * expert_params(cfg) * experts_touched(held(cfg), pairs))
    nbytes = (weights * WEIGHT_BYTES + path[1]
              + (full * context + window * width) * rows
              * cache_entry_bytes(cfg))
    return flops, nbytes


def prefill(cfg: Mapping, prompt_tokens: int, rows: int,
            pairs_a_token: float) -> Tuple[float, float]:
    """``(flops, bytes)`` of absorbing ``prompt_tokens`` real tokens of
    ``rows`` prompts (at their mean length): the layers and the path
    for every token, the head once a row; every weight held read once,
    the full layers' caches written once."""
    full, window, _, sparse = counts(cfg)
    length = prompt_tokens / max(rows, 1)
    path = _path(cfg, prompt_tokens * 2 * cfg["num_hidden_layers"])
    flops = (prompt_tokens * token_flops(cfg)
             + sparse * prompt_tokens * pairs_a_token * 2.0
             * expert_params(cfg)
             + rows * (full * attended_pairs(length, 1) + window * window_read(
                 length, cfg["sliding_window"])) * pair_flops(cfg)
             + rows * 2.0 * head_params(cfg) + path[0])
    weights = (resident_params(cfg) + _path_params(cfg)
               + sparse * held(cfg) * expert_params(cfg))
    nbytes = (weights * WEIGHT_BYTES + path[1]
              + full * prompt_tokens * cache_entry_bytes(cfg))
    return flops, float(nbytes)


def sizes(cfg: Mapping) -> dict:
    """The hand-checkable figures, for PERF.md and the tests."""
    full, window, dense, sparse = counts(cfg)
    return {"mixer_params": mixer_params(cfg),
            "dense_params": dense_params(cfg),
            "expert_params": expert_params(cfg),
            "router_params": router_params(cfg),
            "head_params": head_params(cfg),
            "hc_params": opcount_hc_lm.hc_params(_hc(cfg)),
            "parameters": parameters(cfg),
            "resident_params": resident_params(cfg),
            "layers": {"full": full, "window": window, "dense": dense,
                       "expert": sparse},
            "pair_flops": pair_flops(cfg),
            "position_flops": position_flops(cfg),
            "token_flops": token_flops(cfg)}
