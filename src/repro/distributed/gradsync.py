"""Cross-pod gradient synchronization with int8 compression.

The pod<->pod link (DCN) is the slow hop in a multi-pod mesh; gradients
crossing it are the dominant cross-pod traffic. We quantize each gradient
leaf to int8 with a per-leaf absmax scale before the cross-pod all-reduce and
dequantize after: 4x less DCN traffic for a quantization error well below
SGD noise (Dettmers 2022 lineage; error feedback optional per-step because
the residual is re-quantized every step anyway).

Implementation: a fully-manual shard_map over ALL mesh axes — each device
holds its (data, model)-shard of the fp32 gradient, quantizes locally, psums
the int32-accumulated int8 payload over "pod" only, and rescales. Local
shards stay local; only the pod axis moves bytes.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import partition_specs

PyTree = Any


def _quantize_psum(g: jnp.ndarray) -> jnp.ndarray:
    scale = jnp.max(jnp.abs(g)) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    # accumulate in int16 across pods: exact for up to 258 pods
    # (258 * 127 < 32767), and HALF the wire bytes of an fp32 all-reduce
    # (int32 accumulation would silently nullify the compression).
    qsum = jax.lax.psum(q.astype(jnp.int16), "pod")
    npods = jax.lax.psum(jnp.ones((), jnp.float32), "pod")
    return qsum.astype(jnp.float32) * scale / npods


def int8_psum_grads(grads: PyTree, mesh) -> PyTree:
    """Mean over the pod axis with int8 on-the-wire representation."""
    specs = partition_specs(grads, mesh)

    def sync(*leaves):
        return tuple(_quantize_psum(g) for g in leaves)

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    synced = jax.shard_map(
        sync, mesh=mesh,
        in_specs=tuple(spec_leaves),
        out_specs=tuple(spec_leaves))(*leaves)
    return jax.tree_util.tree_unflatten(treedef, synced)
