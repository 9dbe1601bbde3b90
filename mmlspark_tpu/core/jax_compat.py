"""The jax manual-sharding surface the package is written against.

``pyproject.toml`` pins ``jax>=0.9``, which has the vma-typed
shard_map API natively (``jax.shard_map`` with ``check_vma``,
``jax.lax.pcast``, ``jax.typeof``, ``vma=`` on ``ShapeDtypeStruct``),
so the four helpers below are direct calls. The names stay because
graftlint's GL006/GL008 recognise manual-sharding code by them
(collapsing them into the call sites is ROADMAP D2).
"""

from __future__ import annotations

from typing import Any


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with keyword arguments."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def pcast_varying(x: Any, axes):
    """``jax.lax.pcast(x, axes, to='varying')``; ``axes`` may be one
    name or empty (identity), ``x`` may be a pytree."""
    if not axes:
        return x
    if isinstance(axes, str):
        axes = (axes,)
    import jax

    return jax.lax.pcast(x, tuple(axes), to="varying")


def operand_vma(*operands) -> frozenset:
    """Union of the operands' varying mesh axes."""
    import jax

    vma: frozenset = frozenset()
    for operand in operands:
        vma = vma | getattr(jax.typeof(operand), "vma", frozenset())
    return vma


def shape_dtype_struct(shape, dtype, vma: frozenset = frozenset()):
    """``jax.ShapeDtypeStruct`` carrying ``vma``."""
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
