"""shard_map'd DMD data passes for sharded / stacked buffer leaves.

The flat Pallas kernels (kernels/gram.py, gram_row.py, combine.py) take an
(m, n) buffer — but flattening a GSPMD-sharded buffer forces an all-gather of
the whole thing (measured 59 GiB on a 22-layer stack; DESIGN.md §3), which is
why sharded multi-dim and stacked leaves historically fell back to the
batched dot_general. This module closes that gap (the ROADMAP item): run the
SAME Pallas kernels per shard under `shard_map`, where the reshape is local
and free:

    shard_map(buf sharded per plan.snapshot_spec):
        local flatten (m, n_local)  ->  Pallas kernel, fp32 partial
        -> psum over the axes sharding the contracted dims
           (O(stack·m²) for gram, O(stack·m) for gram_row — tiny)
    combine needs NO psum: c is replicated, the output is sharded exactly
    like the param.

Stacked leaves (scan-over-layers params) vmap the kernel over the collapsed
stack axes — one independent (m, m) Gram per layer, as the paper prescribes.
The anchor subtraction stays fused in-kernel and is shard-local-correct: row
0 of each local tile IS the local slice of the global anchor row. bf16
buffers (`gram_upcast=False`) work unchanged — the kernels upcast per tile in
VMEM, so there is never an HBM-sized fp32 materialization.

Inside shard_map the local call goes through `kernels.ops`, so backend
dispatch still applies: compiled Pallas on TPU, dot_general refs on CPU, and
`ops.set_backend("pallas")` + interpret for the kernel-contract tests. The
shard_map wrapper needs `check_vma=False` (no replication rule exists for
`pallas_call`).

With no mesh on the plan the wrappers degrade to the same local computation
without shard_map — single-host benchmarks and tests share one code path.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops


def _split_stack(x: jnp.ndarray, k: int):
    """(m, s1..sk, rest...) -> (S, m, rest...) with S = prod(stack)."""
    m = x.shape[0]
    stack = x.shape[1:1 + k]
    rest = x.shape[1 + k:]
    xt = jnp.moveaxis(x, 0, k)                    # (s1..sk, m, rest...)
    s_flat = 1
    for d in stack:
        s_flat *= int(d)
    return xt.reshape((s_flat, m) + tuple(rest)), tuple(stack), tuple(rest)


def _local_gram(x, k, anchor_first, block_n, interpret):
    if k == 0:
        return ops.gram(x, anchor_first=anchor_first, block_n=block_n,
                        interpret=interpret)
    xs, stack, _ = _split_stack(x, k)
    g = jax.vmap(lambda s: ops.gram(s, anchor_first=anchor_first,
                                    block_n=block_n, interpret=interpret))(xs)
    m = x.shape[0]
    return g.reshape(stack + (m, m))


def _local_gram_row(x, q, k, anchor_first, block_n, interpret):
    if k == 0:
        return ops.gram_row(x, q, anchor_first=anchor_first, block_n=block_n,
                            interpret=interpret)
    xs, stack, rest = _split_stack(x, k)
    qs = q.reshape((xs.shape[0],) + rest)
    r = jax.vmap(lambda s, qq: ops.gram_row(
        s, qq, anchor_first=anchor_first, block_n=block_n,
        interpret=interpret))(xs, qs)
    return r.reshape(stack + (x.shape[0],))


def _local_combine(x, c, k, block_n, interpret):
    if k == 0:
        return ops.combine(x, c, block_n=block_n, interpret=interpret)
    xs, stack, rest = _split_stack(x, k)
    cs = c.reshape((xs.shape[0], x.shape[0]))
    w = jax.vmap(lambda s, cc: ops.combine(
        s, cc, block_n=block_n, interpret=interpret))(xs, cs)
    return w.reshape(stack + rest)


def _wrap(plan, fn, in_specs, out_specs):
    if plan.mesh is None:
        return fn
    return jax.shard_map(fn, mesh=plan.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def gram(buf: jnp.ndarray, plan, *, anchor_first: bool = False,
         interpret=None) -> jnp.ndarray:
    """(m, stack..., param...) -> (stack..., m, m) fp32 full Gram."""
    k = plan.stack_dims
    axes = plan.psum_axes()

    def local(x):
        g = _local_gram(x, k, anchor_first, plan.block_n, interpret)
        return jax.lax.psum(g, axes) if axes else g

    out_spec = P(*plan.stack_spec_entries, None, None)
    return _wrap(plan, local, (plan.snapshot_spec,), out_spec)(buf)


def gram_row(buf: jnp.ndarray, p: jnp.ndarray, plan, *,
             anchor_first: bool = False, interpret=None) -> jnp.ndarray:
    """(m, stack..., param...), (stack..., param...) -> (stack..., m): the
    streaming row of <d_p, d_j>, one O(stack·m·n_local) pass + psum."""
    k = plan.stack_dims
    axes = plan.psum_axes()

    def local(x, q):
        r = _local_gram_row(x, q, k, anchor_first, plan.block_n, interpret)
        return jax.lax.psum(r, axes) if axes else r

    out_spec = P(*plan.stack_spec_entries, None)
    return _wrap(plan, local, (plan.snapshot_spec, plan.param_spec),
                 out_spec)(buf, p)


def combine(buf: jnp.ndarray, c: jnp.ndarray, plan, *,
            interpret=None) -> jnp.ndarray:
    """(m, stack..., param...), (stack..., m) -> (stack..., param...) fp32.
    Pure local pass: c is replicated and the contraction runs over the
    replicated snapshot axis, so the output inherits the param's sharding
    with zero collectives."""
    k = plan.stack_dims

    def local(x, cc):
        return _local_combine(x, cc, k, plan.block_n, interpret)

    c_spec = P(*plan.stack_spec_entries, None)
    return _wrap(plan, local, (plan.snapshot_spec, c_spec),
                 plan.param_spec)(buf, c)
