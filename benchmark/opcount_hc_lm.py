"""Operations and bytes a decoder of latent-attention layers needs when
its residual is ``hc_mult`` streams a token joined to every sub-layer
by a hyper-connection (``model_type`` ``xing4_0``: mHC,
arXiv:2512.24880): ``opcount_latent_lm``'s counts for the layers
themselves, and the residual path's beside them, from the sizes in the
model's ``config.json`` and from what the program counted
(``hc_sublayer_tokens``: real tokens times the sub-layers they went
through that path around).

As there, these are the yardstick's counts: what the mathematics
requires, not what an implementation executes. A token's way round one
sub-layer is the projection of its ``n C`` normed values onto ``2 n +
n^2`` coefficients, the Sinkhorn rounds on an ``n x n`` matrix, the
read (``n C`` multiply-adds) and the write-back (``n^2 C + n C``); it
has to read the stream once for norm, projection and read, and read and
write it once for the write-back, at the stream's stated dtype
(float32): ``3 n C`` values of four bytes. What the sub-layer reads and
what it returns (``C`` values each) are the sub-layer's own traffic and
are left out of the path's floor; the six-pass float32 product on the
MXU and every further pass over the stream are the implementation's and
are NOT counted.
"""

from __future__ import annotations

from typing import Mapping, Tuple

from benchmark import opcount_latent_lm as latent
from benchmark.opcount_latent_lm import (  # noqa: F401
    WEIGHT_BYTES, held, kinds)

STREAM_BYTES = 4        # the residual's dtype, as the configuration states


def hc_params(cfg: Mapping) -> int:
    """One sub-layer's leaves: ``phi``, ``alpha``, ``b_pre``,
    ``b_post``, ``b_res``. 344,091 as published."""
    n = cfg["hc_mult"]
    return (n * cfg["hidden_size"] * (2 * n + n * n) + 3 + 2 * n + n * n)


def vector_params(cfg: Mapping) -> int:
    """The leaves that are no matrix: two norms a layer, the mixer's
    two, an expert layer's selection bias, the final norm."""
    _, sparse = kinds(cfg)
    routed = cfg.get("router_experts", cfg["n_routed_experts"])
    return (cfg["num_hidden_layers"] * (
        2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"])
        + sparse * routed + cfg["hidden_size"])


def parameters(cfg: Mapping) -> int:
    """Every leaf of the model as this chip holds it. 4,792,669,828 for
    the cut of one dense and five expert layers."""
    return (latent.sizes(cfg)["parameters"] + vector_params(cfg)
            + 2 * cfg["num_hidden_layers"] * hc_params(cfg))


def hc_sublayer(cfg: Mapping) -> Tuple[float, float]:
    """``(flops, bytes)`` of one token's way round one sub-layer.
    890,112 and 172,032 as published."""
    n, width = cfg["hc_mult"], cfg["hidden_size"]
    stream = n * width
    flops = (2.0 * stream * (2 * n + n * n)         # the projection
             + 2.0 * stream                          # the norm's sum
             + cfg["hc_sinkhorn_iters"] * 4.0 * n * n
             + 2.0 * stream                          # the read
             + 2.0 * n * stream + 2.0 * stream)      # the write-back
    return flops, 3.0 * stream * STREAM_BYTES


def hc_path(cfg: Mapping, sublayer_tokens: float) -> Tuple[float, float]:
    """The residual path over ``sublayer_tokens`` (token, sub-layer)
    passages, as the program counted them."""
    flops, nbytes = hc_sublayer(cfg)
    return sublayer_tokens * flops, sublayer_tokens * nbytes


def _sublayers(cfg: Mapping) -> int:
    return 2 * cfg["num_hidden_layers"]


def model_flops(cfg: Mapping, prompt_tokens: int, new_tokens: int,
                rows: int, expert_pairs: int,
                sublayer_tokens: float) -> float:
    """``opcount_latent_lm.model_flops`` and the path's operations."""
    return (latent.model_flops(cfg, prompt_tokens, new_tokens, rows,
                               expert_pairs)
            + hc_path(cfg, sublayer_tokens)[0])


def decode_step(cfg: Mapping, rows: int, context: float,
                pairs_a_token: float) -> Tuple[float, float]:
    """``(flops, bytes)`` of one decode step: the layers', the path's
    for ``rows`` tokens round every sub-layer, its leaves read once."""
    flops, nbytes = latent.decode_step(cfg, rows, context, pairs_a_token)
    path = hc_path(cfg, rows * _sublayers(cfg))
    return (flops + path[0], nbytes + path[1]
            + _sublayers(cfg) * hc_params(cfg) * WEIGHT_BYTES)


def prefill(cfg: Mapping, prompt_tokens: int, rows: int,
            pairs_a_token: float) -> Tuple[float, float]:
    """``(flops, bytes)`` of absorbing ``prompt_tokens`` real tokens of
    ``rows`` prompts."""
    flops, nbytes = latent.prefill(cfg, prompt_tokens, rows, pairs_a_token)
    path = hc_path(cfg, prompt_tokens * _sublayers(cfg))
    return (flops + path[0], nbytes + path[1]
            + _sublayers(cfg) * hc_params(cfg) * WEIGHT_BYTES)


def sizes(cfg: Mapping) -> dict:
    """The hand-checkable figures, for PERF.md and the tests."""
    flops, nbytes = hc_sublayer(cfg)
    return dict(latent.sizes(cfg), hc_params=hc_params(cfg),
                vector_params=vector_params(cfg),
                parameters=parameters(cfg), hc_sublayer_flops=flops,
                hc_sublayer_bytes=nbytes)
