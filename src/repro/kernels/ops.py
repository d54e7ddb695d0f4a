"""Backend dispatch for the DMD data-pass kernels (DESIGN.md §3).

Every DMD entry point (`gram`, `gram_row`, `combine`) routes by backend:

  * TPU  -> the Pallas kernels, COMPILED (interpret=False). The seed
    hard-wired interpret mode everywhere, so the kernels never actually
    compiled even on TPU hardware.
  * CPU/GPU -> the pure `dot_general` references in `ref.py`. These are the
    correctness oracles and XLA already emits optimal code for them; running
    the Pallas interpreter on CPU would be strictly slower.

`flash_attention` is the Pallas flash kernel on every backend (interpreted
off the TPU); `models/attention.py::attend` routes attention itself.

`interpret=True` may still be passed explicitly to force the Pallas kernel
body through the interpreter on any backend — that is the kernel-vs-oracle
contract exercised by tests/test_kernels.py. `set_backend()` is the test /
benchmark override for the automatic routing.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash, ref
from repro.kernels.gram import gram_pallas
from repro.kernels.gram_row import gram_row_pallas
from repro.kernels.combine import combine_pallas

_FORCED_BACKEND: Optional[str] = None


def set_backend(backend: Optional[str]) -> None:
    """Force routing: "pallas" | "ref" | None (auto by jax.default_backend)."""
    global _FORCED_BACKEND
    if backend not in (None, "pallas", "ref"):
        raise ValueError(f"unknown kernel backend {backend!r}")
    _FORCED_BACKEND = backend


def active_backend() -> str:
    if _FORCED_BACKEND is not None:
        return _FORCED_BACKEND
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def pallas_compiled() -> bool:
    """True when the automatic routing runs the DMD kernels as compiled
    Pallas: the Pallas route on a TPU, no interpreter."""
    return active_backend() == "pallas" and not _interp(None)


def _route(interpret) -> str:
    """interpret=None -> backend routing; interpret=True/False -> Pallas with
    that interpreter setting (the explicit kernel-test path)."""
    if interpret is None:
        return active_backend()
    return "pallas"


def _interp(interpret) -> bool:
    """Resolve interpret for a Pallas route: None ("auto", reached via a
    forced set_backend('pallas')) must still interpret off-TPU — compiled
    Pallas only exists on TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


LANES = 128                       # TPU vector-lane width (last-dim tiling)


def lane_block(block_n: int, n: int) -> int:
    """Clamp the requested n-tile to the leaf: a 128-lane multiple no wider
    than the lane-padded leaf itself. The old ``min(block_n, max(n, 128))``
    returned blocks that were NOT lane multiples for 128 < n < block_n
    (n=333 -> block 333) — interpret mode shrugged, compiled TPU Pallas
    requires the multiple. Tiny leaves (n < 128) get one 128-lane tile; the
    wrappers zero-pad to the block, and zero lanes contribute zero to every
    inner product, so padding is exact (tests: tiny-leaf kernel-vs-oracle).

    The ONE home of this invariant: core/leafplan.py sizes plan.block_n with
    it too, so the plan and the kernel wrappers can never disagree."""
    n_pad = max(-(-max(n, 1) // LANES) * LANES, LANES)
    return max(min(block_n // LANES * LANES, n_pad), LANES)


_block = lane_block               # internal call sites


def gram(snapshots: jnp.ndarray, *, anchor_first: bool = False,
         block_n: int = 2048, interpret=None) -> jnp.ndarray:
    """(m, ...) -> (m, m) fp32 full Gram (the recompute / oracle pass).

    The ref route contracts trailing axes in place; only the Pallas route
    flattens (a reshape of a sharded buffer would force an all-gather, and
    on TPU the kernel wants the flat layout anyway)."""
    if _route(interpret) == "ref":
        return ref.gram_ref(snapshots, anchor_first=anchor_first)
    m = snapshots.shape[0]
    flat = snapshots.reshape(m, -1)
    return gram_pallas(flat, anchor_first=anchor_first,
                       block_n=_block(block_n, flat.shape[1]),
                       interpret=_interp(interpret))


def gram_row(snapshots: jnp.ndarray, p: jnp.ndarray, *,
             anchor_first: bool = False, block_n: int = 2048,
             interpret=None) -> jnp.ndarray:
    """(m, ...), (...) -> (m,) streaming Gram row <d_p, d_j> (one O(m*n)
    pass; p is the snapshot just written into its buffer slot)."""
    if _route(interpret) == "ref":
        return ref.gram_row_ref(snapshots, p, anchor_first=anchor_first)
    m = snapshots.shape[0]
    flat = snapshots.reshape(m, -1)
    return gram_row_pallas(flat, p.reshape(-1), anchor_first=anchor_first,
                           block_n=_block(block_n, flat.shape[1]),
                           interpret=_interp(interpret))


def combine(snapshots: jnp.ndarray, c: jnp.ndarray, *, block_n: int = 2048,
            interpret=None) -> jnp.ndarray:
    """(m, ...), (m,) -> (...) = S^T c in fp32."""
    if _route(interpret) == "ref":
        return ref.combine_ref(snapshots, c)
    m = snapshots.shape[0]
    flat = snapshots.reshape(m, -1)
    out = combine_pallas(flat, c,
                         block_n=_block(block_n, flat.shape[1]),
                         interpret=_interp(interpret))
    return out.reshape(snapshots.shape[1:])


def flash_attention(q, k, v, *, causal: bool = True, interpret=None):
    """(B, Sq, H, d), (B, Sk, K, d) -> (B, Sq, H, d); differentiable: the
    Pallas flash kernel with its backward pass (kernels/flash.py).

    It has no reference route: `models/attention.py::attend` chooses
    between it and its own jnp core, which is the kernel's oracle."""
    return flash.flash_attention(q, k, v, causal=causal,
                                 interpret=_interp(interpret))
