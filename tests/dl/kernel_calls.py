"""The latent prefill kernel's calls in a traced prefill: how
``test_latent_lm.py``, ``test_motif_lm.py`` and
``tests/parallel/test_latent.py`` tell the kernel's path from the
scan's, in the program the stage runs or in each layer's own prefill,
and not in a second copy of the mixers' rule."""

import jax
import jax.numpy as jnp


def _latent_prefills(jaxpr):
    """The ``pallas_call``s named ``latent_prefill`` in ``jaxpr``, inside
    its scans and loops included."""
    calls = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls += eqn.params["name"] == "latent_prefill"
        else:
            calls += sum(map(_latent_prefills,
                             jax.core.jaxprs_in_params(eqn.params)))
    return calls


def prefill_kernel_calls(program, params, rows, length):
    """The kernel's calls in the jaxpr of ``program`` (an
    ``lm_prefill``) over ``rows x length`` ids: one a layer that takes
    the kernel, since a step is traced once."""
    closed = jax.make_jaxpr(program)(
        params, jax.ShapeDtypeStruct((rows, length), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int32))
    return _latent_prefills(closed.jaxpr)


def layer_kernel_calls(config, length):
    """The same count a layer at a time, at the config's widths: each
    layer's sequence mixer (a ``DecoderBlock``: the layer) initialised
    alone over one prefill step of one row and ``length`` tokens, and
    the kernel's calls in that trace counted. The mixer chooses its
    kernel from the step's shape alone, so the sum is what the whole
    model's ``lm_prefill`` holds; a mixer equal to one traced before
    (the same kind over the same config) counts as that one did."""
    from mmlspark_tpu.dl.backbones import HybridBlock, HybridLM, lm_module

    c = lm_module(config).config
    x = jax.ShapeDtypeStruct((1, length, c["hidden_size"]), jnp.float32)
    positions = jax.ShapeDtypeStruct((1, length), jnp.int32)
    lengths = jax.ShapeDtypeStruct((1,), jnp.int32)
    traced, calls = [], 0
    for block in HybridLM._blocks(c):
        layer = (block.mixer()(c) if isinstance(block, HybridBlock)
                 else block)
        seen = [n for other, n in traced if other == layer]
        if not seen:
            state = jax.eval_shape(lambda b=block: b.init_state(1, length))
            seen = [_latent_prefills(jax.make_jaxpr(layer.init)(
                jax.random.key(0), x, positions, lengths, state).jaxpr)]
            traced.append((layer, seen[0]))
        calls += seen[0]
    return calls
