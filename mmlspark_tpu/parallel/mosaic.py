"""What the language models' kernels share: whether this backend runs
the Mosaic kernels, and the rounding of a product's operands that their
``jax.numpy`` twins use."""

from __future__ import annotations


def use_pallas() -> bool:
    """The Mosaic kernels on the TPU backend, ``jax.numpy`` elsewhere."""
    import jax

    return jax.default_backend() == "tpu"


def operand(x, dtype):
    """``x`` as an operand of a product in ``dtype``, kept float32 (a
    pair of converts XLA may drop as excess precision)."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    if jnp.dtype(dtype) == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)
