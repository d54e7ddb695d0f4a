"""The benchmark's weight generator: one jitted call from a key, in the
dtype each leaf is trained in. Program and reference both start from it, so
the reference takes no weights the program made."""
from __future__ import annotations

from typing import Callable


def make(key, shapes, rule: Callable[[str, tuple], str]):
    """``maker(shapes, rule)(key)``."""
    return maker(shapes, rule)(key)


def maker(shapes, rule: Callable[[str, tuple], str]):
    """A compiled ``key -> weights`` shaped like ``shapes`` (a pytree of
    ShapeDtypeStruct).
    ``rule(path, shape)`` names each leaf's init: ``"ones"``, ``"zeros"``,
    ``"fan_in"`` (normal / sqrt(shape[-2])) or ``"xavier"`` (normal *
    sqrt(2 / (shape[-2] + shape[-1])))."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    kinds = [rule(jax.tree_util.keystr(p), tuple(s.shape)) for p, s in flat]

    def build(key):
        out = []
        for i, ((_, s), kind) in enumerate(zip(flat, kinds)):
            shape, dt = tuple(s.shape), s.dtype
            if kind == "ones":
                out.append(jnp.ones(shape, dt))
            elif kind == "zeros":
                out.append(jnp.zeros(shape, dt))
            else:
                z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                if kind == "fan_in":
                    std = 1.0 / (shape[-2] if len(shape) >= 2
                                 else shape[-1]) ** 0.5
                elif kind == "xavier":
                    std = (2.0 / (shape[-2] + shape[-1])) ** 0.5
                else:
                    raise ValueError(f"unknown init {kind!r}")
                out.append((z * std).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)
