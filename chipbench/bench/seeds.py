"""PRNG keys from the run's ``--seed``, which may exceed 32 bits."""
from __future__ import annotations


def key_for(seed: int, *path: int):
    """A key for ``seed`` (any non-negative int below 2**64), folded with
    ``path``: each 32-bit half of the seed is folded in on its own."""
    import jax
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key
