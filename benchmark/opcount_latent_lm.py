"""Operations and bytes a decoder of latent-attention layers over a
dense SwiGLU or sparse experts needs (``model_type`` ``kimi_k2``: the
DeepSeek-V3 layer in every layer), computed from the sizes in its
``config.json`` and from what the program counted: the (token, expert)
pairs it served and the cache positions it filled.

As in ``opcount.py`` and ``opcount_hybrid_lm.py`` these are the
yardstick's counts: what the mathematics requires, not what an
implementation executes. A routed expert costs its three matrices for
each pair routed to an expert held here; attention over the prompt is
counted in its expanded form (``2 (nope + rope) + 2 d_v`` a pair and
head) and a decode step's in its absorbed form, which is what a cache
of latents makes possible (``2 (rank + rope) + 2 rank`` a cached
position and head: the latent is key and value at once); padded
positions, positions a row has not filled, re-expanded cache blocks and
lane padding are the implementation's and are NOT counted. Weights are
two bytes a parameter (the configuration's bfloat16) and are read once
a step, an expert only where a pair touches it; a cached position is
two bytes a value and is read once a decode step.
"""

from __future__ import annotations

from typing import Mapping, Tuple

# what does not depend on the kind of mixer is opcount_hybrid_lm's: the
# experts held, a SwiGLU's, an expert's, the router's and the head's
# parameters, a cached position's bytes, a row's attended pairs, the
# experts a step's pairs touch
from benchmark.opcount_hybrid_lm import (  # noqa: F401
    WEIGHT_BYTES, attended_pairs, cache_entry_bytes, dense_params,
    expert_params, experts_touched, head_params, held, router_params)


def kinds(cfg: Mapping) -> Tuple[int, int]:
    """``(dense, expert)`` layers; every layer mixes by latent
    attention."""
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], layers)
    return dense, layers - dense


def latent_params(cfg: Mapping) -> int:
    """Matrices of a latent-attention mixer (no gate). 101,122,048 at
    the published widths."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rank, d_v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * qk
            + h * (rank + cfg["qk_rope_head_dim"])
            + rank * heads * (cfg["qk_nope_head_dim"] + d_v)
            + heads * d_v * h)


def expert_layer_params(cfg: Mapping) -> int:
    """An expert layer as this chip holds it: the mixer, the router,
    the shared expert and the experts held. 676,397,056 with 12 held."""
    shared = cfg.get("n_shared_experts", 1) * expert_params(cfg)
    return (latent_params(cfg) + router_params(cfg) + shared
            + held(cfg) * expert_params(cfg))


def cache_position_bytes(cfg: Mapping) -> int:
    """One position of one sequence over every layer: the whole
    per-sequence state. 5,760 with 5 layers."""
    return cfg["num_hidden_layers"] * cache_entry_bytes(cfg)


def prefill_pair_flops(cfg: Mapping) -> float:
    """One (query, key) pair over all heads, expanded form."""
    return cfg["num_attention_heads"] * 2.0 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def decode_position_flops(cfg: Mapping) -> float:
    """One cached position against one token's queries over all heads,
    absorbed form: the score over ``rank + rope`` values and the
    weighted sum over ``rank``. 139,264 as published."""
    return cfg["num_attention_heads"] * 2.0 * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def token_flops(cfg: Mapping) -> float:
    """One token through every layer, without the routed experts and
    without the attention's pairs: two operations a matrix parameter."""
    dense, sparse = kinds(cfg)
    shared = cfg.get("n_shared_experts", 1) * expert_params(cfg)
    return 2.0 * (cfg["num_hidden_layers"] * latent_params(cfg)
                  + dense * dense_params(cfg)
                  + sparse * (router_params(cfg) + shared))


def decode_positions(prompt_tokens: float, rows: int, steps: int) -> float:
    """Cached positions the decode steps of one call attend to, a
    layer: step ``s`` (of ``steps``) of a row reads its prompt and the
    ``s`` tokens generated so far."""
    return steps * prompt_tokens + rows * steps * (steps + 1) / 2.0


def model_flops(cfg: Mapping, prompt_tokens: int, new_tokens: int,
                rows: int, expert_pairs: int) -> float:
    """A batch generation: every prompt token and every new token but
    each row's last through the layers, ``expert_pairs`` pairs through
    an expert, the head once a row for the prompt and once for every
    new token but the last; the prompt's pairs in the expanded form
    (reckoned at the rows' mean length: no more than the true sum), the
    decode steps' in the absorbed form."""
    layers = cfg["num_hidden_layers"]
    through = prompt_tokens + new_tokens - rows
    steps = new_tokens // max(rows, 1) - 1
    prefill_pairs = rows * attended_pairs(prompt_tokens / max(rows, 1), 1)
    return (through * token_flops(cfg)
            + expert_pairs * 2.0 * expert_params(cfg)
            + layers * prefill_pairs * prefill_pair_flops(cfg)
            + layers * decode_positions(prompt_tokens, rows, steps)
            * decode_position_flops(cfg)
            + new_tokens * 2.0 * head_params(cfg))


def resident_params(cfg: Mapping) -> int:
    """Parameters every step reads whatever is routed. 1,235,943,424
    for the cut (2.47 GB)."""
    dense, sparse = kinds(cfg)
    shared = cfg.get("n_shared_experts", 1) * expert_params(cfg)
    return (cfg["num_hidden_layers"] * latent_params(cfg)
            + dense * dense_params(cfg)
            + sparse * (router_params(cfg) + shared) + head_params(cfg))


def decode_step(cfg: Mapping, rows: int, context: float,
                pairs_a_token: float) -> Tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences whose
    caches hold ``context`` positions each; ``pairs_a_token``: pairs an
    expert layer serves a token (counted). Resident weights and each
    touched expert read once, every cached position read once."""
    _, sparse = kinds(cfg)
    layers = cfg["num_hidden_layers"]
    pairs = rows * pairs_a_token                    # a layer a step
    flops = (rows * (token_flops(cfg) + 2.0 * head_params(cfg))
             + sparse * pairs * 2.0 * expert_params(cfg)
             + layers * rows * context * decode_position_flops(cfg))
    weights = resident_params(cfg) + sparse * expert_params(cfg) \
        * experts_touched(held(cfg), pairs)
    nbytes = (weights * WEIGHT_BYTES
              + rows * context * cache_position_bytes(cfg))
    return flops, nbytes


def prefill(cfg: Mapping, prompt_tokens: int, rows: int,
            pairs_a_token: float) -> Tuple[float, float]:
    """``(flops, bytes)`` of absorbing ``prompt_tokens`` real tokens of
    ``rows`` prompts: the layers for every token, the head once a row;
    every weight held read once, the cache written once."""
    _, sparse = kinds(cfg)
    layers = cfg["num_hidden_layers"]
    length = prompt_tokens / max(rows, 1)
    flops = (prompt_tokens * token_flops(cfg)
             + sparse * prompt_tokens * pairs_a_token * 2.0
             * expert_params(cfg)
             + layers * rows * attended_pairs(length, 1)
             * prefill_pair_flops(cfg)
             + rows * 2.0 * head_params(cfg))
    weights = resident_params(cfg) + sparse * held(cfg) * expert_params(cfg)
    nbytes = (weights * WEIGHT_BYTES
              + prompt_tokens * cache_position_bytes(cfg))
    return flops, float(nbytes)


def latent_decode(cfg: Mapping, positions: float) -> Tuple[float, float]:
    """The decode kernel over ``positions`` filled cache positions
    (summed over its launches and rows): each read once, as key and as
    value. The queries and the output (0.2 MB a row and launch beside a
    cache of 0.1-1.5 MB) are left out of the floor."""
    return (positions * decode_position_flops(cfg),
            positions * float(cache_entry_bytes(cfg)))


def sizes(cfg: Mapping) -> dict:
    """The hand-checkable figures, for PERF.md and the tests."""
    dense, sparse = kinds(cfg)
    return {"latent_params": latent_params(cfg),
            "dense_params": dense_params(cfg),
            "expert_params": expert_params(cfg),
            "router_params": router_params(cfg),
            "head_params": head_params(cfg),
            "expert_layer_params": expert_layer_params(cfg),
            "parameters": (
                dense * (latent_params(cfg) + dense_params(cfg))
                + sparse * expert_layer_params(cfg)
                + 2 * head_params(cfg)),
            "resident_params": resident_params(cfg),
            "cache_position_bytes": cache_position_bytes(cfg),
            "token_flops": token_flops(cfg)}
