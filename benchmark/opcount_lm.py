"""Operations and bytes a power-retention language model needs,
computed from the sizes in its ``config.json``.

As in ``opcount.py`` these are the yardstick's counts: what the
mathematics requires, not what an implementation executes. The state
of a key-value head is the ``d (d + 1) / 2`` distinct products of a
key (8256 at ``d = 128``), float32, read and written once a token; the
program's packing into 65 rows of 128, padded rows and padded
positions, multi-pass float32 products and the one-hot products that
build the symmetric square are the implementation's and are NOT
counted. Weights are two bytes a parameter (the configuration's
bfloat16) and are read once a step.
"""

from __future__ import annotations

from typing import Dict, Mapping, Tuple

WEIGHT_BYTES = 2
STATE_BYTES = 4


def phi_width(d: int) -> int:
    """Distinct products ``x_a x_b``, ``a <= b``."""
    return d * (d + 1) // 2


def layer_params(cfg: Mapping) -> int:
    """Parameters of one decoder layer: q, k, v and o projections, the
    gate's matrix (its bias of one value a key-value head is not
    counted), the three SwiGLU matrices, the two RMSNorm scales over the
    hidden size and the two over a head. 330,352,896 at the published
    widths."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (2 * h * heads * d + 2 * h * kv * d + h * kv
            + 3 * h * cfg["intermediate_size"] + 2 * h + 2 * d)


def head_params(cfg: Mapping) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def state_bytes(cfg: Mapping) -> int:
    """Bytes of one layer's retention state ``S`` for one sequence:
    kv heads x distinct products x value width, float32. 33,816,576 at
    the published widths."""
    d = cfg["head_dim"]
    return cfg["num_key_value_heads"] * phi_width(d) * d * STATE_BYTES


def norm_state_bytes(cfg: Mapping) -> int:
    """The normaliser ``z`` beside it: kv heads x distinct products."""
    return (cfg["num_key_value_heads"] * phi_width(cfg["head_dim"])
            * STATE_BYTES)


def retention_token_flops(cfg: Mapping) -> float:
    """One token through one layer's recurrence: a key-value head
    scales its state, adds ``phi(k) v^T`` (3 d D) and moves ``z``
    (2 D); a query head reads ``S`` (2 d D) and ``z`` (2 D); each
    vector's symmetric square is 2 D."""
    d = cfg["head_dim"]
    wide = phi_width(d)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return float(kv * (3 * d * wide + 2 * wide) + heads * (2 * d * wide
                 + 2 * wide) + (heads + kv) * 2 * wide)


def layer_token_flops(cfg: Mapping) -> float:
    """One token through one layer: two operations a matrix parameter
    and the recurrence."""
    matrices = layer_params(cfg) - 2 * cfg["hidden_size"] \
        - 2 * cfg["head_dim"]
    return 2.0 * matrices + retention_token_flops(cfg)


def model_flops(cfg: Mapping, prompt_tokens: int, new_tokens: int,
                rows: int) -> float:
    """A batch generation: every prompt token and every new token but
    each row's last through the layers; the head once a row for the
    prompt and once for every new token but the last."""
    layers = cfg["num_hidden_layers"]
    through = prompt_tokens + new_tokens - rows
    return (through * layers * layer_token_flops(cfg)
            + new_tokens * 2.0 * head_params(cfg))


def decode_step(cfg: Mapping, rows: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences:
    every weight read once (the layers and the head; the embedding's
    ``rows`` rows are nothing beside them), every state read and
    written once."""
    layers = cfg["num_hidden_layers"]
    flops = rows * (layers * layer_token_flops(cfg)
                    + 2.0 * head_params(cfg))
    weights = (layers * layer_params(cfg) + head_params(cfg)) * WEIGHT_BYTES
    state = 2.0 * rows * layers * (state_bytes(cfg) + norm_state_bytes(cfg))
    return flops, weights + state


def prefill(cfg: Mapping, prompt_tokens: int, rows: int
            ) -> Tuple[float, float]:
    """``(flops, bytes)`` of absorbing ``prompt_tokens`` real tokens of
    ``rows`` prompts: the layers for every token, the head once a row;
    the layers' weights and the head read once, the state written once."""
    layers = cfg["num_hidden_layers"]
    flops = (prompt_tokens * layers * layer_token_flops(cfg)
             + rows * 2.0 * head_params(cfg))
    weights = (layers * layer_params(cfg) + head_params(cfg)) * WEIGHT_BYTES
    state = rows * layers * (state_bytes(cfg) + norm_state_bytes(cfg))
    return flops, float(weights + state)


def retention_decode(cfg: Mapping, rows: int) -> Tuple[float, float]:
    """One launch of the decode kernel (one layer, ``rows`` sequences):
    the recurrence's operations; ``S`` read and written once, q, k, v
    and y beside it."""
    d = cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    nbytes = rows * (2.0 * state_bytes(cfg)
                     + (2 * heads + 2 * kv) * d * STATE_BYTES)
    return rows * retention_token_flops(cfg), nbytes


def retention_prefill(cfg: Mapping, rows: int, chunk: int
                      ) -> Tuple[float, float]:
    """One launch of the prefill kernel (one layer, one chunk of
    ``chunk`` tokens of ``rows`` sequences) in the chunked form: inside
    the chunk the causal half of the scores and of the weighted values
    (2 d each a pair and query head), across chunks one read of the
    state a query (2 d D) and one update a key (2 d D), the state scaled
    once (d D); ``S`` read and written once a chunk."""
    d = cfg["head_dim"]
    wide = phi_width(d)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = chunk * (chunk + 1) // 2
    flops = rows * (heads * (pairs * 4.0 * d + chunk * 2.0 * d * wide)
                    + kv * (chunk * 2.0 * d * wide + d * wide))
    nbytes = rows * (2.0 * state_bytes(cfg)
                     + chunk * (2 * heads + 2 * kv) * d * STATE_BYTES)
    return flops, nbytes


def sizes(cfg: Mapping) -> Dict[str, float]:
    """The hand-checkable figures, for PERF.md and the tests."""
    return {"layer_params": layer_params(cfg),
            "head_params": head_params(cfg),
            "state_bytes": state_bytes(cfg),
            "layer_token_flops": layer_token_flops(cfg)}
