"""Operations and bytes a decoder of grouped-query attention over a plain
key/value cache needs, its layers full or over a window with sink
logits, over a dense SwiGLU or sparse experts (``model_type``
``mimo_v2``), from the sizes in the model's ``config.json`` (MiMo's own
keys) and from what the program counted: the (token, expert) pairs it
served and the cached positions the window layers' attention read
(``window_positions``).

As in ``opcount_gdla_lm.py`` these are the yardstick's counts: what the
mathematics requires, not what an implementation executes. A (query,
key) pair costs every query head ``2 head_dim + 2 v_head_dim``
operations of its kind; a full layer's query at position ``p`` reads
``p + 1`` positions, a window layer's ``min(p + 1, sliding_window)``.
Padded positions, the blocks a kernel reads past a row's own fill for
another row of its group, the key blocks a window prefill reads beyond
a query's window, the queries laid on every key head's lanes and lane
padding are the implementation's and are NOT counted, nor is
element-wise work (rotation, the sink's term, norms, the router's
sigmoid). Weights are two bytes a parameter (the configuration's
bfloat16) and are read once a step, an expert only where a pair
touches it; a cached position is two bytes a value (its key heads' keys
and values) and is read once a decode step, a full layer's cache to the
context, a window layer's ring to its window.
"""

from __future__ import annotations

from typing import Mapping, Tuple

from benchmark.opcount_hybrid_lm import (  # noqa: F401
    WEIGHT_BYTES, attended_pairs, dense_params, expert_params,
    experts_touched, head_params)


def layer_kinds(cfg: Mapping):
    """``[(window, sparse), ...]`` a layer, by the published index the
    layer stands for (``published_layers``): ``hybrid_layer_pattern`` 1
    a window layer, ``moe_layer_freq`` 1 an expert layer."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        p = cfg.get("published_layers", range(cfg["num_hidden_layers"]))[i]
        out.append((cfg["hybrid_layer_pattern"][p] == 1,
                    cfg["moe_layer_freq"][p] == 1))
    return out


def counts(cfg: Mapping) -> Tuple[int, int, int, int]:
    """``(full, window, dense, expert)`` layers: (2, 5, 1, 6) for the
    cut."""
    kinds = layer_kinds(cfg)
    window = sum(w for w, _ in kinds)
    sparse = sum(s for _, s in kinds)
    return len(kinds) - window, window, len(kinds) - sparse, sparse


def widths(cfg: Mapping, window: bool) -> Tuple[int, int, int, int]:
    """``(heads, key heads, head_dim, v_head_dim)`` of a kind: (64, 4,
    192, 128) full, (64, 8, 192, 128) window as published."""
    def get(key):
        return cfg.get("swa_" + key, cfg[key]) if window else cfg[key]
    return (get("num_attention_heads"), get("num_key_value_heads"),
            get("head_dim"), get("v_head_dim"))


def held(cfg: Mapping) -> int:
    first, stop = cfg.get("experts_held", (0, cfg["n_routed_experts"]))
    return stop - first


def router_params(cfg: Mapping) -> int:
    """The router over every published expert: 1,048,576."""
    return cfg["hidden_size"] * cfg.get("router_experts",
                                        cfg["n_routed_experts"])


def attention_params(cfg: Mapping, window: bool) -> int:
    """A mixer's two matrices, the fused query, key and value projection
    and the output: 89,128,960 full and 94,371,840 window as
    published."""
    heads, key_heads, d, d_v = widths(cfg, window)
    h = cfg["hidden_size"]
    return h * (heads * d + key_heads * (d + d_v)) + heads * d_v * h


def sinks(cfg: Mapping, window: bool) -> int:
    kind = "swa" if window else "full"
    return (widths(cfg, window)[0]
            if cfg.get(f"add_{kind}_attention_sink_bias") else 0)


def parameters(cfg: Mapping) -> int:
    """Every leaf of the model as this chip holds it: the mixers (and
    their sink logits), the dense MLP, the router and its selection bias
    and the held experts, two norms a layer, the embedding, the head
    and the final norm. 3,429.9M for the cut."""
    full, window, dense, sparse = counts(cfg)
    routed = cfg.get("router_experts", cfg["n_routed_experts"])
    return (full * (attention_params(cfg, False) + sinks(cfg, False))
            + window * (attention_params(cfg, True) + sinks(cfg, True))
            + dense * dense_params(cfg)
            + sparse * (router_params(cfg) + routed
                        + held(cfg) * expert_params(cfg))
            + cfg["num_hidden_layers"] * 2 * cfg["hidden_size"]
            + 2 * head_params(cfg) + cfg["hidden_size"])


def resident_params(cfg: Mapping) -> int:
    """Matrices every step reads whatever is routed."""
    full, window, dense, sparse = counts(cfg)
    return (full * attention_params(cfg, False)
            + window * attention_params(cfg, True)
            + dense * dense_params(cfg) + sparse * router_params(cfg)
            + head_params(cfg))


def pair_flops(cfg: Mapping, window: bool) -> float:
    """One (query, key) pair over every query head of a kind: 40,960 as
    published (both kinds)."""
    heads, _, d, d_v = widths(cfg, window)
    return heads * 2.0 * (d + d_v)


def entry_bytes(cfg: Mapping, window: bool) -> int:
    """One cached position of one layer: its key heads' keys and values,
    2,560 B full and 5,120 B window as published."""
    _, key_heads, d, d_v = widths(cfg, window)
    return WEIGHT_BYTES * key_heads * (d + d_v)


def token_flops(cfg: Mapping) -> float:
    """One token through every layer, without the routed experts and the
    attention's pairs: two operations a matrix parameter (the head
    apart)."""
    return 2.0 * (resident_params(cfg) - head_params(cfg))


def decode_positions(prompt_tokens: float, rows: int, steps: int) -> float:
    """Positions a full layer's decode steps read over a call: at step
    ``s`` (1 .. ``steps``) a row of ``P`` prompt tokens reads ``P + s``
    (its prompt, the tokens fed before and its own)."""
    return steps * prompt_tokens + rows * steps * (steps + 1) / 2.0


def decode_window_positions(cfg: Mapping, rows: int, steps: int) -> float:
    """What the decode steps of a call read in the window layers, rows
    whose prompts are a window long or more: a window a step."""
    _, window, _, _ = counts(cfg)
    return window * rows * steps * cfg["sliding_window"]


def model_flops(cfg: Mapping, prompt_tokens: int, new_tokens: int,
                rows: int, expert_pairs: int,
                window_positions: float) -> float:
    """A batch generation: every prompt token and every new token but
    each row's last through the layers, ``expert_pairs`` pairs through
    an expert, the head once a row for the prompt and once for every new
    token but the last; a full layer's pairs at the rows' mean length
    (no more than the true sum: the count is convex in the length), a
    window layer's reads as counted."""
    full, _, _, _ = counts(cfg)
    through = prompt_tokens + new_tokens - rows
    pairs = rows * attended_pairs(prompt_tokens / max(rows, 1),
                                  new_tokens / max(rows, 1))
    return (through * token_flops(cfg)
            + expert_pairs * 2.0 * expert_params(cfg)
            + full * pairs * pair_flops(cfg, False)
            + window_positions * pair_flops(cfg, True)
            + new_tokens * 2.0 * head_params(cfg))


def kv_decode(cfg: Mapping, full_positions: float, window_positions: float,
              launches: float) -> Tuple[float, float]:
    """``(flops, bytes)`` the decode kernel needs for ``full_positions``
    positions read in full layers and ``window_positions`` in window
    layers over ``launches`` (a layer's step of one row): each position's
    keys and values read once, a row's query (the model's dtype) read and
    its output (float32) written once a launch."""
    heads, _, d, d_v = widths(cfg, False)
    flops = (full_positions * pair_flops(cfg, False)
             + window_positions * pair_flops(cfg, True))
    nbytes = (full_positions * entry_bytes(cfg, False)
              + window_positions * entry_bytes(cfg, True)
              + launches * heads * (WEIGHT_BYTES * d + 4 * d_v))
    return flops, float(nbytes)


def kv_prefill(cfg: Mapping, prompt_tokens: float,
               rows: int) -> Tuple[float, float]:
    """``(flops, bytes)`` the full layers' prefill kernel needs for
    ``prompt_tokens`` real tokens of ``rows`` prompts (at their mean
    length, no more than the true sum): each prompt position attends to
    itself and all before it; each position's keys and values read once,
    the queries read and the outputs written once."""
    full, _, _, _ = counts(cfg)
    heads, _, d, d_v = widths(cfg, False)
    flops = (full * rows * attended_pairs(prompt_tokens / max(rows, 1), 1)
             * pair_flops(cfg, False))
    nbytes = full * prompt_tokens * (entry_bytes(cfg, False)
                                     + heads * (WEIGHT_BYTES * d + 4 * d_v))
    return flops, float(nbytes)


def decode_step(cfg: Mapping, rows: int, context: float,
                pairs_a_token: float) -> Tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences whose
    caches hold ``context`` positions each; ``pairs_a_token``: pairs an
    expert layer serves a token (counted). Resident weights and each
    touched expert read once; a full layer's cache to the context, a
    window layer's ring to its window."""
    full, window, _, sparse = counts(cfg)
    width = min(context, cfg["sliding_window"])
    pairs = rows * pairs_a_token                    # a layer a step
    flops = (rows * (token_flops(cfg) + 2.0 * head_params(cfg))
             + sparse * pairs * 2.0 * expert_params(cfg)
             + rows * (full * context * pair_flops(cfg, False)
                       + window * width * pair_flops(cfg, True)))
    weights = (resident_params(cfg) + sparse * expert_params(cfg)
               * experts_touched(held(cfg), pairs))
    nbytes = (weights * WEIGHT_BYTES
              + rows * (full * context * entry_bytes(cfg, False)
                        + window * width * entry_bytes(cfg, True)))
    return flops, float(nbytes)


def sizes(cfg: Mapping) -> dict:
    """The hand-checkable figures, for PERF.md and the tests."""
    full, window, dense, sparse = counts(cfg)
    return {"full_attention_params": attention_params(cfg, False),
            "window_attention_params": attention_params(cfg, True),
            "dense_params": dense_params(cfg),
            "expert_params": expert_params(cfg),
            "router_params": router_params(cfg),
            "head_params": head_params(cfg),
            "parameters": parameters(cfg),
            "resident_params": resident_params(cfg),
            "layers": {"full": full, "window": window, "dense": dense,
                       "expert": sparse},
            "full_entry_bytes": entry_bytes(cfg, False),
            "window_entry_bytes": entry_bytes(cfg, True),
            "pair_flops": pair_flops(cfg, False),
            "token_flops": token_flops(cfg)}
