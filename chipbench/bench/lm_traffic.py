"""Training batches for a token model, generated from the seed on the
device: the general generator that the LM training cells' traffic files
parameterise. Tokens are Zipf-skewed (u^3 scaled to the vocabulary, as the
launcher's synthetic stream draws them); an encoder-decoder also gets
(batch, frames, d_model) float32 frame embeddings."""
from __future__ import annotations

from typing import List, Optional


def batch(key, *, batch: int, seq: int, vocab: int,
          frames: Optional[tuple] = None) -> dict:
    import jax
    import jax.numpy as jnp

    k_tok, k_fr = jax.random.split(key)
    u = jax.random.uniform(k_tok, (batch, seq + 1), minval=1e-6, maxval=1.0)
    ids = (u ** 3.0 * vocab).astype(jnp.int32) % vocab
    out = {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
    if frames is not None:
        out["frames"] = jax.random.normal(k_fr, (batch,) + tuple(frames),
                                          jnp.float32)
    return out


def ring(key, n: int, **shape) -> List[dict]:
    """``n`` distinct batches, batch ``i`` drawn from ``fold_in(key, i)``,
    made by one compiled call and left on the device."""
    import jax

    def make(key):
        return [batch(jax.random.fold_in(key, i), **shape) for i in range(n)]

    return jax.jit(make)(key)


def one(key, i: int, **shape) -> dict:
    """Batch ``i`` of ``ring(key, ...)``, alone."""
    import jax
    return jax.jit(lambda k: batch(jax.random.fold_in(k, i), **shape))(key)
