"""Operations and bytes a hybrid decoder needs (delta-rule and
latent-attention layers over a dense SwiGLU or sparse experts),
computed from the sizes in its ``config.json`` and from the (token,
expert) pairs the program counted.

As in ``opcount.py`` and ``opcount_lm.py`` these are the yardstick's
counts: what the mathematics requires, not what an implementation
executes. A routed expert costs its three matrices for each pair
routed to an expert held here, and nothing for a pair routed elsewhere
or for a padded row of a tile; latent attention is counted in its
expanded form (keys and values expanded once a position, ``2 (nope +
rope) + 2 d_v`` a pair and head), whatever form the program runs;
padded positions, multi-pass float32 products, the triangular solve's
padding and re-expanded cache blocks are the implementation's and are
NOT counted. Weights are two bytes a parameter (the configuration's
bfloat16) and are read once a step, an expert only where a pair
touches it; the delta-rule state is float32, read and written once a
token; a cached position is read once a decode step.
"""

from __future__ import annotations

from typing import Mapping, Tuple

WEIGHT_BYTES = 2
STATE_BYTES = 4


def kinds(cfg: Mapping) -> Tuple[int, int, int, int]:
    """``(delta-rule, latent, dense, expert)`` layers."""
    layers = cfg["num_hidden_layers"]
    latent = sum(i in cfg["full_attention_layers"] for i in range(layers))
    dense = min(cfg["first_k_dense_replace"], layers)
    return layers - latent, latent, dense, layers - dense


def held(cfg: Mapping) -> int:
    first, stop = cfg.get("experts_held", (0, cfg["n_routed_experts"]))
    return stop - first


def delta_params(cfg: Mapping) -> int:
    """Matrices of a delta-rule mixer: q, k (key heads), v, z (value
    heads), b, a (one a value head), the output projection, the
    convolution's taps. 235,864,064 at the published widths."""
    h = cfg["hidden_size"]
    qk = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    v = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return (h * (2 * qk + 2 * v + 2 * cfg["linear_num_value_heads"])
            + v * h + cfg["linear_conv_kernel_dim"] * (2 * qk + v))


def latent_params(cfg: Mapping) -> int:
    """Matrices of a latent-attention mixer, its output gate included.
    159,842,304 at the published widths."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rank, d_v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    gate = h * heads * d_v if cfg.get("gated_attention") else 0
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * qk
            + h * (rank + cfg["qk_rope_head_dim"])
            + rank * heads * (cfg["qk_nope_head_dim"] + d_v)
            + heads * d_v * h + gate)


def dense_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: Mapping) -> int:
    """One expert's three matrices. 44,040,192 at the published widths."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: Mapping) -> int:
    return cfg["hidden_size"] * cfg.get("router_experts",
                                        cfg["n_routed_experts"])


def head_params(cfg: Mapping) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def delta_state_bytes(cfg: Mapping) -> int:
    """One delta-rule layer's state for one sequence: ``S`` (value heads
    x d_k x d_v) and the convolution's tail, float32. 4,390,912 at the
    published widths."""
    qk = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    v = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return STATE_BYTES * (
        v * cfg["linear_key_head_dim"]
        + (cfg["linear_conv_kernel_dim"] - 1) * (2 * qk + v))


def cache_entry_bytes(cfg: Mapping) -> int:
    """One cached position of one latent layer: 1,152 as published."""
    return WEIGHT_BYTES * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def delta_token_flops(cfg: Mapping) -> float:
    """One token through one layer's recurrence: a value head decays
    its state (d_k d_v), reads it for the key (2 d_k d_v), adds the
    outer product (2 d_k d_v) and reads it for the query (2 d_k d_v)."""
    return 7.0 * (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
                  * cfg["linear_value_head_dim"])


def attention_pair_flops(cfg: Mapping) -> float:
    """One (query, key) pair over all heads, expanded form."""
    return cfg["num_attention_heads"] * 2.0 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def token_flops(cfg: Mapping) -> float:
    """One token through every layer, without the routed experts and
    without the attention's pairs: two operations a matrix parameter,
    and the delta rule's recurrence."""
    delta, latent, dense, sparse = kinds(cfg)
    shared = cfg.get("n_shared_experts", 1) * expert_params(cfg)
    return (2.0 * (delta * delta_params(cfg) + latent * latent_params(cfg)
                   + dense * dense_params(cfg)
                   + sparse * (router_params(cfg) + shared))
            + delta * delta_token_flops(cfg))


def attended_pairs(length: float, new: float) -> float:
    """(query, key) pairs of one row of ``length`` prompt tokens that
    generates ``new``: every position but the last attends to itself
    and all before it."""
    through = length + new - 1
    return through * (through + 1) / 2.0


def model_flops(cfg: Mapping, prompt_tokens: int, new_tokens: int,
                rows: int, expert_pairs: int) -> float:
    """A batch generation: every prompt token and every new token but
    each row's last through the layers, ``expert_pairs`` pairs through
    an expert, the head once a row for the prompt and once for every
    new token but the last. The attention's pairs are reckoned at the
    rows' mean length: no more than the true sum (the count is convex
    in the length), so a share computed from it is never too high."""
    _, latent, _, _ = kinds(cfg)
    through = prompt_tokens + new_tokens - rows
    pairs = rows * attended_pairs(prompt_tokens / max(rows, 1),
                                  new_tokens / max(rows, 1))
    return (through * token_flops(cfg)
            + expert_pairs * 2.0 * expert_params(cfg)
            + latent * pairs * attention_pair_flops(cfg)
            + new_tokens * 2.0 * head_params(cfg))


def _resident_weights(cfg: Mapping) -> float:
    """Parameters every step reads whatever is routed."""
    delta, latent, dense, sparse = kinds(cfg)
    shared = cfg.get("n_shared_experts", 1) * expert_params(cfg)
    return (delta * delta_params(cfg) + latent * latent_params(cfg)
            + dense * dense_params(cfg)
            + sparse * (router_params(cfg) + shared) + head_params(cfg))


def experts_touched(count: int, pairs: float) -> float:
    """Experts of ``count`` that ``pairs`` pairs, spread evenly at
    random, touch: 15.7 of 16 at 64 pairs."""
    return count * (1.0 - (1.0 - 1.0 / count) ** pairs) if count else 0.0


def decode_step(cfg: Mapping, rows: int, context: float,
                pairs_a_token: float) -> Tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences whose
    caches hold ``context`` positions each; ``pairs_a_token``: pairs an
    expert layer serves a token (counted). Resident weights and each
    touched expert read once, every delta-rule state read and written
    once, every cached position read once."""
    delta, latent, _, sparse = kinds(cfg)
    pairs = rows * pairs_a_token                    # a layer a step
    flops = (rows * (token_flops(cfg) + 2.0 * head_params(cfg))
             + sparse * pairs * 2.0 * expert_params(cfg)
             + latent * rows * context * attention_pair_flops(cfg))
    weights = _resident_weights(cfg) + sparse * expert_params(cfg) \
        * experts_touched(held(cfg), pairs)
    nbytes = (weights * WEIGHT_BYTES
              + 2.0 * rows * delta * delta_state_bytes(cfg)
              + rows * latent * context * cache_entry_bytes(cfg))
    return flops, nbytes


def prefill(cfg: Mapping, prompt_tokens: int, rows: int,
            pairs_a_token: float) -> Tuple[float, float]:
    """``(flops, bytes)`` of absorbing ``prompt_tokens`` real tokens of
    ``rows`` prompts: the layers for every token, the head once a row;
    every weight held read once, the state and the cache written once."""
    delta, latent, _, sparse = kinds(cfg)
    length = prompt_tokens / max(rows, 1)
    flops = (prompt_tokens * token_flops(cfg)
             + sparse * prompt_tokens * pairs_a_token * 2.0
             * expert_params(cfg)
             + latent * rows * attended_pairs(length, 1)
             * attention_pair_flops(cfg)
             + rows * 2.0 * head_params(cfg))
    weights = _resident_weights(cfg) + sparse * held(cfg) \
        * expert_params(cfg)
    nbytes = (weights * WEIGHT_BYTES
              + rows * delta * delta_state_bytes(cfg)
              + latent * prompt_tokens * cache_entry_bytes(cfg))
    return flops, float(nbytes)


def gdn_decode(cfg: Mapping, rows: int) -> Tuple[float, float]:
    """One launch of the decode kernel (one layer, ``rows`` sequences):
    the recurrence's operations; ``S`` read and written once, the key,
    the query, the value and the output beside it, the decay and the
    write strength one value a head."""
    heads = cfg["linear_num_value_heads"]
    d_k, d_v = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    nbytes = rows * heads * STATE_BYTES * (
        2.0 * d_k * d_v + 2 * d_k + 2 * d_v + 2)
    return rows * delta_token_flops(cfg), nbytes


def sizes(cfg: Mapping) -> dict:
    """The hand-checkable figures, for PERF.md and the tests."""
    delta, latent, dense, sparse = kinds(cfg)
    shared = cfg.get("n_shared_experts", 1) * expert_params(cfg)
    return {"delta_params": delta_params(cfg),
            "latent_params": latent_params(cfg),
            "dense_params": dense_params(cfg),
            "expert_params": expert_params(cfg),
            "head_params": head_params(cfg),
            "parameters": (
                delta * delta_params(cfg) + latent * latent_params(cfg)
                + dense * dense_params(cfg)
                + sparse * (router_params(cfg) + shared
                            + held(cfg) * expert_params(cfg))
                + 2 * head_params(cfg)),
            "delta_state_bytes": delta_state_bytes(cfg),
            "token_flops": token_flops(cfg)}
