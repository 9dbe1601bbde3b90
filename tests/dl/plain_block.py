"""``HybridBlock`` and ``HybridLM.hidden`` as they were before the
residual could be several streams (PR 38's: ``h = h + sub(norm(h))``
through ``added()``, one vector a token), kept as the oracle for "a
config without ``hc_mult`` builds the programs it built":
``test_hyper_lm.py`` holds the parameter trees and the jaxpr text of
``lm_prefill`` and ``lm_generate`` under the two to be equal."""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from mmlspark_tpu.dl.backbones import (
    DeltaMixer, DenseFeedForward, ExpertFeedForward, HybridLM, LatentMixer,
    _setting, block_norm)


class PlainBlock(nn.Module):
    config: Any
    index: int

    def latent(self) -> bool:
        listed = self.config.get("full_attention_layers")
        return listed is None or self.index in tuple(listed)

    def sparse(self) -> bool:
        return self.index >= self.config["first_k_dense_replace"]

    @nn.compact
    def __call__(self, h, positions, lengths, state):
        c = self.config
        post = _setting(c, "layernorm_type") == "pre_post"

        def added(y, name):
            return h + (block_norm(self, name, y, c) if post else y)

        mixer = LatentMixer if self.latent() else DeltaMixer
        with jax.named_scope("lm.mla" if self.latent() else "lm.gdn"):
            y, state = mixer(c, name="mixer")(
                block_norm(self, "mixer_pre", h, c), positions, lengths,
                state)
            h = added(y, "mixer_post")
        ffn = ExpertFeedForward if self.sparse() else DenseFeedForward
        with jax.named_scope("lm.moe" if self.sparse() else "lm.mlp"):
            valid = jnp.arange(h.shape[1])[None, :] < lengths[:, None]
            y, served = ffn(c, name="ffn")(
                block_norm(self, "ffn_pre", h, c), valid)
            h = added(y, "ffn_post")
        return h, state, served


class PlainLM(HybridLM):
    @staticmethod
    def _blocks(config):
        return [PlainBlock(config, i, name=f"layers_{i}")
                for i in range(config["num_hidden_layers"])]

    def hidden(self, ids, lengths, state, every=False):
        with jax.named_scope("lm.embed"):
            h = jnp.take(self.embedding, ids, axis=0).astype(jnp.float32)
        positions = state["pos"][:, None] + jnp.arange(ids.shape[1])
        layers, pairs = [], []
        dropped = state["experts"]["dropped"]
        for block, layer_state in zip(self.layers, state["layers"]):
            h, layer_state, served = block(h, positions, lengths,
                                           layer_state)
            layers.append(layer_state)
            if served is not None:
                pairs.append(served[0])
                dropped = dropped + served[1]
        if not every:
            with jax.named_scope("lm.last"):
                last = jnp.clip(lengths - 1, 0, ids.shape[1] - 1)
                h = jnp.take_along_axis(h, last[:, None, None],
                                        axis=1)[:, 0]
        served = state["experts"]["pairs"]
        if pairs:
            served = served + jnp.stack(pairs)
        return h, {"pos": state["pos"] + lengths, "layers": layers,
                   "experts": {"pairs": served, "dropped": dropped}}
