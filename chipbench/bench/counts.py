"""Operations and bytes the algorithms need, from shapes alone.

These count the work of the algorithm, not of an implementation: a later
kernel or model change is read against the same numbers. Recomputation,
embedding gathers, norms and softmax are not counted.
"""
from __future__ import annotations

from typing import Tuple


def encdec_train_flops(*, batch: int, seq: int, frames: int, d_model: int,
                       d_ff: int, vocab: int, enc_layers: int,
                       dec_layers: int) -> float:
    """Forward + backward matmul FLOPs of one encoder-decoder training step
    (backward = twice the forward). Per layer and token: the four attention
    projections 8 d^2, the MLP 4 d d_ff, and 4 d per attended (query, key)
    pair; causal self-attention attends S(S+1)/2 pairs, the encoder and the
    cross-attention every pair. Cross-attention's K/V projections run once
    per encoder frame and decoder layer; the tied output head is 2 d V per
    decoder token."""
    d, f, F, S = d_model, d_ff, frames, seq
    enc = F * (8 * d * d + 4 * d * f + 4 * F * d) * enc_layers
    dec_tok = 8 * d * d + 4 * d * f + 4 * d * d + 4 * F * d
    dec = (S * dec_tok + 4 * d * S * (S + 1) // 2 + 4 * d * d * F) \
        * dec_layers
    head = 2 * d * vocab * S
    return 3.0 * batch * (enc + dec + head)


def gram_row_cost(m: int, n: int, itemsize: int) -> Tuple[float, float]:
    """(flops, bytes) of one streaming Gram row over ``m`` snapshot rows of
    ``n`` lanes: every stored snapshot is read once and dotted with the new
    one."""
    return 2.0 * m * n, float(m * n * itemsize)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at best: the larger of the compute bound and
    the memory bound."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
