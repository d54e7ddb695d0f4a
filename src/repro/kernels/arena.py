"""Segmented DMD data passes over packed leaf arenas (DESIGN.md §7).

The per-leaf kernels (gram.py / gram_row.py / combine.py, plus their
shard_map wrappers in sharded.py) pay one launch PER LEAF per pass — a
transformer config with hundreds of DMD-managed leaves pays hundreds of tiny
dispatches per recorded step. An arena (core/arena.py) packs every
compatible leaf of a schedule group into ONE contiguous BLOCK-MAJOR
``(n_blocks, m, block_n)`` snapshot buffer: the lane axis is split into
``block_n``-lane blocks, each block carries all ``m`` snapshot rows of its
lanes contiguously, and every per-system segment is padded to a block
multiple so no block ever straddles two systems. The kernels here then walk
the whole arena in a single launch:

  * ``gram_row``  (nb, m, bn), (nb, bn)       -> (n_sys, m)    streaming rows
  * ``gram``      (nb, m, bn)                 -> (n_sys, m, m) full recompute
  * ``combine``   (nb, m, bn), (n_sys, m)     -> (N,)          the jump blend

Block-major is the load-bearing layout choice, on every backend at once:

  * CPU/GPU: the block axis is a LEADING batch dimension, so each pass is
    one batched ``dot_general`` that XLA lowers straight to the gemm/gemv
    library (batch dims must lead a batched contraction — with the old
    snapshot-major ``(m, N)`` layout the same contraction forced either a
    full-buffer transpose or a poorly-vectorized fused multiply-reduce,
    measured ~2.5x slower for the streaming row pass on a deep MLP).
  * TPU: the Pallas tile IS the storage tile — block ``i`` of the grid maps
    to ``x[i]`` with no re-tiling, and the (m, block_n) VMEM tile keeps
    the lane axis on the 128-wide minor dimension.
  * The every-step resident record writes one ``(nb, 1, bn)`` slab per
    bucket (``dynamic_update_slice`` on the middle axis) — still a single
    fused op per bucket.

Segmentation is driven by a static ``block_sys`` table mapping each block
to its system index (a "system" = one independent DMD trajectory: an
unstacked leaf, or one stacked layer of a scan-stacked leaf). On TPU the
table rides in scalar-prefetch memory (``PrefetchScalarGridSpec``) and
indexes the OUTPUT BlockSpec: consecutive blocks of the same system revisit
the same (1, m)/(1, m, m) output tile, so the per-system reduction
accumulates in-place in VMEM with zero extra bandwidth — the classic
ragged/segmented grid pattern. The CPU/GPU reference route computes
per-block partials with one batched ``dot_general`` and reduces them with
one ``segment_sum`` — still a single fused XLA op chain, which is the whole
point: O(buckets) dispatches instead of O(leaves).

Padding is exact everywhere for the same reason as the flat kernels: tail
lanes of every segment are zero in the arena (core/arena.py packs them so),
zero lanes contribute zero to every inner product, and the anchor row's
padding is itself zero. The anchor subtraction stays fused: snapshot row 0
of every block IS that block's anchor slice, because all systems in a
bucket share one slot schedule (same group).

Sharded buckets (every leaf sharded over the SAME mesh axes on contracted
dims) reuse sharded.py's pattern: the same local kernels run per shard
under ``shard_map`` on the locally-packed arena (the BLOCK axis is sharded
— shard boundaries are always block boundaries because every shard's lane
count is a block_n multiple), followed by one O(n_sys·m²)/O(n_sys·m) psum
for the Gram passes; ``combine`` needs no collective at all.

Backend dispatch matches kernels/ops.py: compiled Pallas on TPU, the
reference route on CPU/GPU, explicit ``interpret=`` for the
kernel-vs-oracle contract tests.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels import ops


def snapshot_rows(m: int, dtype) -> int:
    """Stored snapshot rows of a block-major buffer: ``m`` rounded up to
    the TPU sublane tile of ``dtype`` (8 rows of 4 bytes, 16 of 2). A
    (nb, m, bn) buffer whose m is off the tile is laid out m-major on the
    TPU, and every Pallas call (which wants the row-major tile) would copy
    the whole buffer first; at the tile it is read in place. The tile
    padding costs no HBM — the physical layout pads m to the tile anyway.
    Rows >= m stay zero and are never written."""
    tile = 32 // jnp.dtype(dtype).itemsize
    return -(-m // tile) * tile


# ---------------------------------------------------------------------------
# Reference route (CPU/GPU oracle): one batched dot_general + one
# segment_sum per pass, block axis leading
# ---------------------------------------------------------------------------

def gram_row_ref(x: jnp.ndarray, q: jnp.ndarray, block_sys, n_sys: int, *,
                 anchor_first: bool = False, block_n: int,
                 m: Optional[int] = None) -> jnp.ndarray:
    """(nb, rows, bn), (nb, bn) -> (n_sys, m) of <d_q, d_j> per system
    (``m`` real rows of the ``rows`` stored; default all).

    Always contracts in fp32, exactly like the per-leaf kernel oracles
    (kernels/ref.py) and the per-tile upcast in the Pallas bodies — the
    upcast fuses into the contraction, so there is no reason to degrade
    bf16 storage further (cfg.gram_upcast only shapes the dot_general
    fallback route, which arenas never take).

    Anchoring uses the partials identity instead of materializing the
    anchored buffer: with qa = q - x0,

        <qa, x_j - x_0> = <qa, x_j> - <qa, x_0>

    so only q is anchored (one (nb, bn) subtract), the batched dot runs on
    the RAW buffer — one streaming read, no (nb, m, bn)-sized anchored
    temporary — and column 0 of the raw partials is subtracted afterwards.
    The identity is algebraic, so it is exact on the dyadic trajectories
    the route-equality pins use; under fp rounding it differs from
    explicit anchoring only by summation-order effects, inside the
    kernel-contract tolerances (the Pallas tile body anchors explicitly in
    VMEM, where the subtract costs no bandwidth)."""
    del block_n                         # implied by the block-major shape
    xf = x.astype(jnp.float32)          # (nb, m, bn)
    qf = q.astype(jnp.float32)          # (nb, bn)
    if anchor_first:
        qf = qf - xf[:, 0, :]
    part = jax.lax.dot_general(
        xf, qf, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                   # (nb, m)
    if anchor_first:
        part = part - part[:, 0:1]
    return jax.ops.segment_sum(part[:, :m], jnp.asarray(block_sys),
                               num_segments=n_sys, indices_are_sorted=True)


def gram_ref(x: jnp.ndarray, block_sys, n_sys: int, *,
             anchor_first: bool = False, anchor_mean: bool = False,
             block_n: int, m: Optional[int] = None) -> jnp.ndarray:
    """(nb, rows, bn) -> (n_sys, m, m) full Grams, one per system (fp32
    contraction regardless of storage dtype — see gram_row_ref).

    ``anchor_mean`` subtracts the per-lane snapshot mean before the
    contraction (dmd.gram_matrix's mean path, fp32 like its upcast
    route). Pad lanes are zero, their mean is zero, so padding stays
    exact. Mutually exclusive with ``anchor_first``; mean buckets have
    no streaming row pass (dmd.gram_row_matrix rejects mean), so only
    this full-recompute kernel carries the flag. The once-per-rebuild
    pass anchors explicitly (an (nb, m, bn) fused subtract) — the m×m
    partials of the part-anchor identity don't pay for themselves here."""
    if anchor_first and anchor_mean:
        raise ValueError("anchor_first and anchor_mean are exclusive")
    del block_n
    m = x.shape[1] if m is None else m
    xf = x.astype(jnp.float32)          # (nb, rows, bn)
    if anchor_first:
        xf = xf - xf[:, 0:1, :]
    if anchor_mean:
        # rows >= m are zero: sum/m is the mean of the real rows
        xf = xf - jnp.sum(xf, axis=1, keepdims=True) / m
    part = jax.lax.dot_general(
        xf, xf, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                   # (nb, rows, rows)
    return jax.ops.segment_sum(part[:, :m, :m], jnp.asarray(block_sys),
                               num_segments=n_sys, indices_are_sorted=True)


def combine_ref(x: jnp.ndarray, c: jnp.ndarray, block_sys, *,
                block_n: int) -> jnp.ndarray:
    """(nb, m, bn), (n_sys, m) -> (N,) = S^T c_sys per lane's own system.

    Always fp32, like the per-leaf ref.combine_ref — downcasting the
    coefficients to bf16 storage dtype would silently break the
    arena-vs-per-leaf oracle contract on gram_upcast=False configs
    (the per-leaf kernel route never does).

    A batched dot_general contracting the snapshot axis: same m-reduction
    order as the per-leaf tensordot, so the two routes stay BIT-identical
    whenever the coefficient solves agree (pinned by the
    integer-trajectory test). Block-major makes this a batch-leading
    gemv — no transpose at all, where the old (m, N) layout paid one per
    jump."""
    del block_n
    xf = x.astype(jnp.float32)                                # (nb, m, bn)
    cb = c.astype(jnp.float32)[jnp.asarray(block_sys)]        # (nb, m)
    out = jax.lax.dot_general(
        cb[:, None, :], xf, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                   # (nb, 1, bn)
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# Pallas TPU kernels: one launch per arena, the grid tile IS the storage
# tile x[i], output tile indexed by the prefetched block->system table,
# in-place accumulation across revisits
# ---------------------------------------------------------------------------

def _row_kernel(seg_ref, x_ref, q_ref, out_ref, *, anchor_first: bool):
    i = pl.program_id(0)
    first = jnp.logical_or(i == 0,
                           seg_ref[i] != seg_ref[jnp.maximum(i - 1, 0)])
    x = x_ref[0].astype(jnp.float32)              # (rows, block_n)
    q = q_ref[...].astype(jnp.float32)            # (tile, block_n)
    if anchor_first:
        q = q - x[0:1, :]
        x = x - x[0:1, :]
    full = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (tile, rows)
    # block i's query row is row i % tile of the (tile, block_n) q block
    # (rows of other blocks — or past the array's end — are masked out)
    pick = jax.lax.broadcasted_iota(jnp.int32, full.shape, 0) == \
        i % q.shape[0]
    part = jnp.sum(jnp.where(pick, full, 0.0), axis=0,
                   keepdims=True)[None]            # (1, 1, rows)

    @pl.when(first)
    def _init():
        out_ref[...] = part

    @pl.when(jnp.logical_not(first))
    def _acc():
        out_ref[...] += part


# Block shapes: the TPU tiling rule wants each block's last two dims to be
# (8, 128)-divisible OR equal to the array's. The snapshot block is
# (1, rows, block_n) — rows equals the array's own row count, and is the
# sublane tile multiple (snapshot_rows), so the buffer is read in place.
# Per-system vectors (the coefficients, the Gram-row outputs) are carried
# as 3-D (n_sys, 1, width) arrays whose trailing (1, width) block equals
# the array's own trailing dims. The (nb, bn) query row is read as its
# natural (tile, bn) sublane tiles — a (1, bn) block is refused, and a 3-D
# (nb, 1, bn) view would cost a relayout copy of q per call — and each
# block picks its own row out of the tile.

@functools.partial(jax.jit, static_argnames=("n_sys", "anchor_first",
                                             "block_n", "m", "interpret"))
def gram_row_pallas(x: jnp.ndarray, q: jnp.ndarray, block_sys, n_sys: int, *,
                    anchor_first: bool = False, block_n: int,
                    m: Optional[int] = None,
                    interpret: bool = True) -> jnp.ndarray:
    """(nb, rows, bn), (nb, bn) -> (n_sys, m): the kernel walks every
    stored row (``rows`` >= m, see snapshot_rows); ``m`` (default: all
    rows) keeps the real ones."""
    nb, rows, _ = x.shape
    m = rows if m is None else m
    tile = 32 // jnp.dtype(q.dtype).itemsize       # q's sublane tile rows
    out = pl.pallas_call(
        functools.partial(_row_kernel, anchor_first=anchor_first),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[pl.BlockSpec((1, rows, block_n),
                                   lambda i, s: (i, 0, 0)),
                      pl.BlockSpec((tile, block_n),
                                   lambda i, s: (i // tile, 0))],
            out_specs=pl.BlockSpec((1, 1, rows), lambda i, s: (s[i], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_sys, 1, rows), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(block_sys, jnp.int32), x, q)
    return out[:, 0, :m]


def _gram_kernel(seg_ref, x_ref, out_ref, *, anchor_first: bool,
                 mean_rows: int):
    i = pl.program_id(0)
    first = jnp.logical_or(i == 0,
                           seg_ref[i] != seg_ref[jnp.maximum(i - 1, 0)])
    x = x_ref[0].astype(jnp.float32)              # (rows, block_n)
    if anchor_first:
        x = x - x[0:1, :]
    if mean_rows:
        # mean anchoring: rows >= m are zero so sum/m is the exact
        # per-lane mean; subtracting it contaminates only those rows, whose
        # Gram entries land at indices >= m and are sliced away.
        x = x - jnp.sum(x, axis=0, keepdims=True) / mean_rows
    part = jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)[None]  # (1, rows, rows)

    @pl.when(first)
    def _init():
        out_ref[...] = part

    @pl.when(jnp.logical_not(first))
    def _acc():
        out_ref[...] += part


@functools.partial(jax.jit, static_argnames=("n_sys", "anchor_first",
                                             "anchor_mean", "block_n", "m",
                                             "interpret"))
def gram_pallas(x: jnp.ndarray, block_sys, n_sys: int, *,
                anchor_first: bool = False, anchor_mean: bool = False,
                block_n: int, m: Optional[int] = None,
                interpret: bool = True) -> jnp.ndarray:
    """(nb, rows, bn) -> (n_sys, m, m); ``m`` as in gram_row_pallas."""
    if anchor_first and anchor_mean:
        raise ValueError("anchor_first and anchor_mean are exclusive")
    nb, rows, _ = x.shape
    m = rows if m is None else m
    out = pl.pallas_call(
        functools.partial(_gram_kernel, anchor_first=anchor_first,
                          mean_rows=m if anchor_mean else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[pl.BlockSpec((1, rows, block_n),
                                   lambda i, s: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, rows, rows),
                                   lambda i, s: (s[i], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_sys, rows, rows), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(block_sys, jnp.int32), x)
    return out[:, :m, :m]


def _combine_kernel(seg_ref, c_ref, x_ref, out_ref):
    del seg_ref                                   # consumed by the index maps
    x = x_ref[0].astype(jnp.float32)              # (rows, block_n)
    c = c_ref[0]                                  # (1, rows)
    out_ref[...] = jax.lax.dot_general(
        c, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (1, block_n)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def combine_pallas(x: jnp.ndarray, c: jnp.ndarray, block_sys, *,
                   block_n: int, interpret: bool = True) -> jnp.ndarray:
    """(nb, rows, bn), (n_sys, m) -> (N,); rows >= m are zero-weighted."""
    nb, rows, _ = x.shape
    n_sys, m = c.shape
    c = c.astype(jnp.float32)
    if rows != m:
        c = jnp.pad(c, ((0, 0), (0, rows - m)))   # (n_sys, rows): tiny
    out = pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[pl.BlockSpec((1, 1, rows), lambda i, s: (s[i], 0, 0)),
                      pl.BlockSpec((1, rows, block_n),
                                   lambda i, s: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, block_n), lambda i, s: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, nb * block_n), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(block_sys, jnp.int32),
      c.reshape(n_sys, 1, rows), x)
    return out[0]


# ---------------------------------------------------------------------------
# Dispatch (kernels/ops.py contract) + shard_map wrappers for sharded buckets
# ---------------------------------------------------------------------------

# Every entry of a real row's result reads only real rows, so the Gram
# passes run over all stored rows and slice (slicing the buffer instead
# would duplicate the record's in-place row write into the reading
# fusion and cost a whole-buffer copy). combine contracts over the rows,
# so its ref route contracts only the m real ones: the oracle's reduction
# order then does not depend on the stored padding.

def _local_gram_row(x, q, block_sys, n_sys, anchor_first, block_n, m,
                    interpret):
    if ops._route(interpret) == "ref":
        return gram_row_ref(x, q, block_sys, n_sys,
                            anchor_first=anchor_first, block_n=block_n, m=m)
    return gram_row_pallas(x, q, block_sys, n_sys, anchor_first=anchor_first,
                           block_n=block_n, m=m,
                           interpret=ops._interp(interpret))


def _local_gram(x, block_sys, n_sys, anchor_first, anchor_mean, block_n, m,
                interpret):
    if ops._route(interpret) == "ref":
        return gram_ref(x, block_sys, n_sys,
                        anchor_first=anchor_first, anchor_mean=anchor_mean,
                        block_n=block_n, m=m)
    return gram_pallas(x, block_sys, n_sys, anchor_first=anchor_first,
                       anchor_mean=anchor_mean, block_n=block_n, m=m,
                       interpret=ops._interp(interpret))


def _local_combine(x, c, block_sys, block_n, interpret):
    if ops._route(interpret) == "ref":
        return combine_ref(x[:, :c.shape[-1]], c, block_sys, block_n=block_n)
    return combine_pallas(x, c, block_sys, block_n=block_n,
                          interpret=ops._interp(interpret))


def shard_wrap(mesh, lane_axes: Tuple[str, ...], fn, in_specs, out_specs):
    """sharded.py's shard_map pattern: no mesh (or a one-device mesh) ->
    the local computation IS the global one; otherwise run per shard — a
    replicated bucket (no ``lane_axes``) runs whole on every device, since
    the partitioner cannot split a Pallas call itself. The ONE home of the
    arena shard_map contract — core/arena.py's pack/unpack wraps through
    this too, so the kernel path and the data-layout path can never
    diverge."""
    if mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def lane_spec(lane_axes: Tuple[str, ...]) -> P:
    """PartitionSpec of an arena's FLAT 1-D lane axis — the leaf-wise
    pack/unpack rows and the combine output (shared with core/arena.py's
    ArenaBucket.lane_spec). Block-major SNAPSHOT buffers shard the same
    mesh axes over their leading block axis instead: see buf_spec."""
    return P(lane_axes if len(lane_axes) > 1 else
             (lane_axes[0] if lane_axes else None))


def _axis_entry(axes: Tuple[str, ...]):
    """One PartitionSpec entry for a (possibly multi-axis) mesh axis set."""
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def buf_spec(axes: Tuple[str, ...]) -> P:
    """PartitionSpec of a block-major (n_blocks, m, block_n) snapshot
    buffer: the mesh axes that sharded the old flat lane axis shard the
    leading BLOCK axis (every shard's lane count is a block_n multiple,
    so shard boundaries are always block boundaries and the global
    (N,) -> (nb, bn) reshape splits the sharded dim divisibly)."""
    return P(_axis_entry(axes), None, None)


def gram_row(buf: jnp.ndarray, q: jnp.ndarray, block_sys, n_sys: int, *,
             anchor_first: bool = False, block_n: int,
             m: Optional[int] = None,
             mesh=None, lane_axes: Tuple[str, ...] = (),
             sys_axes: Tuple[str, ...] = (),
             interpret=None) -> jnp.ndarray:
    """One streaming Gram row per system, ONE launch for the whole arena.
    ``buf`` is block-major (nb, rows, bn) holding ``m`` real snapshot rows
    (default: all rows; see snapshot_rows) and ``q`` its blocked query row
    (nb, bn). ``block_sys`` is the (shard-local) block->system table and
    ``n_sys`` the shard-LOCAL system count. Lane-sharded buckets
    (``lane_axes``) run per shard + one O(n_sys·m) psum; system-sharded
    buckets (``sys_axes`` — a scan-stacked leaf whose stacked dim is
    sharded) need NO collective: each shard owns whole systems, and the
    output stays sharded over its system axis."""
    axes = sys_axes + lane_axes
    m = buf.shape[1] if m is None else m

    def local(x, qq):
        r = _local_gram_row(x, qq, block_sys, n_sys, anchor_first, block_n,
                            m, interpret)
        return jax.lax.psum(r, lane_axes) if lane_axes else r

    return shard_wrap(mesh, axes, local,
                 (buf_spec(axes), P(_axis_entry(axes), None)),
                 P(_axis_entry(sys_axes), None))(buf, q)


def gram(buf: jnp.ndarray, block_sys, n_sys: int, *,
         anchor_first: bool = False, anchor_mean: bool = False,
         block_n: int, m: Optional[int] = None, mesh=None,
         lane_axes: Tuple[str, ...] = (), sys_axes: Tuple[str, ...] = (),
         interpret=None) -> jnp.ndarray:
    """Full (n_sys, m, m) Gram recompute, ONE launch + one O(n_sys·m²) psum
    over the lane axes (the non-streaming A/B path and the
    restore-staleness rebuild). System-sharded outputs stay sharded."""
    axes = sys_axes + lane_axes
    m = buf.shape[1] if m is None else m

    def local(x):
        g = _local_gram(x, block_sys, n_sys, anchor_first, anchor_mean,
                        block_n, m, interpret)
        return jax.lax.psum(g, lane_axes) if lane_axes else g

    return shard_wrap(mesh, axes, local,
                 (buf_spec(axes),),
                 P(_axis_entry(sys_axes), None, None))(buf)


def combine(buf: jnp.ndarray, c: jnp.ndarray, block_sys, *,
            block_n: int, mesh=None,
            lane_axes: Tuple[str, ...] = (),
            sys_axes: Tuple[str, ...] = (), interpret=None) -> jnp.ndarray:
    """(N,) fp32 jump blend, ONE launch, zero collectives: ``c`` is
    (n_sys, m) over the buffer's m real rows, replicated
    over the lane axes (sharded over the system axes, matching the Gram
    stack) and every block contracts only its own system's replicated
    snapshot axis, so the flat output inherits the arena's lane sharding."""
    axes = sys_axes + lane_axes

    def local(x, cc):
        return _local_combine(x, cc, block_sys, block_n, interpret)

    ls = lane_spec(axes)
    return shard_wrap(mesh, axes, local,
                 (buf_spec(axes), P(_axis_entry(sys_axes), None)),
                 ls)(buf, c)
