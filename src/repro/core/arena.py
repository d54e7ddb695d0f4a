"""Packed leaf arenas: one buffer, one launch, one solve per bucket (§7).

The paper's speedup argument is operation-count reduction, but the realized
wall-clock of the per-leaf pipeline is dominated by *dispatch*: every
DMD-managed leaf pays its own ``record`` / ``gram_row`` / ``combine``
kernel launch via tree_map and its own tiny (m, m) eigensolve, so a
transformer config with hundreds of leaves pays hundreds of launches per
recorded step and a long unrolled jitted trace. The Koopman-mode view
(Manojlović et al.) and Turjeman et al.'s correlated-dynamics observation
both treat the whole weight state as one dynamical system — which is also
exactly the layout that runs fastest on hardware: one contiguous buffer,
one kernel, one batched solve.

This module buckets all compatible leaves at accelerator init —

    bucket key = (schedule group, param dtype, lane-sharding axes)

— into one contiguous arena per bucket, with an offset/length table
(``ArenaSegment``) carried on the ``ArenaBucket`` alongside the LeafPlan
pytree. Per-system segments (a "system" = one independent DMD trajectory:
an unstacked leaf, or one layer of a scan-stacked leaf) are padded to a
multiple of the bucket's ``block_n`` (itself a 128-lane multiple), so the
segmented kernels in kernels/arena.py can walk the whole arena in ONE
launch with no block ever straddling systems; tail lanes are zero and
contribute zero to every inner product (padding is exact).

State layout (TrainState.dmd_buffers / dmd_gram when arenas are active):

    {"__arena__": {bucket_key: (n_blocks, m, block_n) ring buffer}, "leaf": …}
    {"__arena__": {bucket_key: (n_sys, m, m) fp32 Grams},           "leaf": …}

The snapshot ring buffer is BLOCK-MAJOR: the flat lane axis is cut into
``block_n``-lane blocks and each block carries its own m snapshot rows
contiguously. That single layout decision makes every DMD data pass a
batch-LEADING contraction (one gemm/gemv-shaped ``dot_general`` per
bucket on CPU/GPU — batch dims must lead, so the old snapshot-major
(m, N) layout forced either a full-buffer transpose or a slow fused
multiply-reduce) and makes the TPU Pallas tile literally the storage
tile ``x[i]``. The every-step record writes one (nb, 1, bn) slab per
bucket; flat (N,) rows appear only at the pack/unpack and jump-blend
boundaries, where blocking is a free divisible reshape.

The ``leaf`` subtree keeps the per-leaf layout for leaves an arena cannot
take (route forced to ``dot_general``, or a stack axis sharded on a
non-leading dim) — the two routes coexist leaf-by-leaf. ``dmd.arena=False``
disables bucketing entirely and keeps the bit-exact per-leaf A/B oracle.

Parameter residency (``dmd.arena_native``, DESIGN.md §7): during
``Trainer.fit`` the managed params (and elementwise optimizer moments) of
packed leaves live IN their bucket's contiguous ``(N_local,)`` device
buffer — the same wrapper layout as the snapshot state:

    {"__arena__": {bucket_key: (N,) flat params}, "leaf": pytree-with-None}

``tree_resident`` / ``tree_leafwise`` convert between the two layouts;
``tree_leafwise`` doubles as the in-trace view expansion for the model's
forward (static slice + reshape per segment — zero-copy views of the
contiguous buffer, no scatter). With resident params, ``record`` is one
``astype`` + ``dynamic_update_slice`` per bucket (a pointer bump) instead
of the per-leaf pack gather, and ``jump`` writes the blended flat row
straight back as the new resident buffer.

Sharded-stack leaves (scan-stacked params whose leading stack dim is
sharded) pack into their own SINGLE-SEGMENT bucket per leaf: each device
owns whole systems (``sys_axes``), the Gram stack stays sharded
``P(sys_axes, None, None)``, and the kernels need no collective beyond
the usual lane psum. ``anchor=mean`` buckets run the full-recompute Gram
kernel with fused mean subtraction (streaming is structurally off for
mean — dmd.gram_row_matrix rejects it).

Jump solve: instead of one ``eigh``/``_host_eig`` call per leaf,
``jump`` concatenates every bucket's Grams of a jumping group into one
(n_sys_total, m, m) batch and makes ONE ``dmd_coefficients`` call per
group (``m`` is uniform within a group by construction — the group's
schedule sizes every member's window), then splits the coefficient rows
back per bucket for the single segmented combine launch.

Checkpoint compatibility: arenas are serialized LEAF-WISE
(``buffers_leafwise`` / ``grams_leafwise`` and their inverses) — the
Trainer unpacks arenas into the per-leaf pytree before ``save_checkpoint``
and re-packs after restore, so checkpoints are byte-identical between
arena on/off, pre-arena checkpoints load unchanged, and elastic restore
onto a remapped mesh keeps using the audited per-leaf PartitionSpecs.
Pack/unpack is lossless (pad lanes are zero on both sides).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import dmd as dmd_math
from repro.core.leafplan import LeafPlan, plan_entries
from repro.core.schedule import GroupSchedule
from repro.core.snapshots import _static_int

PyTree = Any

ARENA_KEY = "__arena__"


@dataclass(frozen=True)
class ArenaSegment:
    """One leaf's slice of a bucket's lane axis (the offset/length table).

    A leaf with k stack dims contributes ``n_sys`` consecutive systems,
    each occupying ``seg_lanes`` lanes (``flat_local`` real + zero tail).
    ``*_local`` fields and ``n_sys`` are shard-local for sharded buckets
    (every device holds the same layout over its own shards; for a
    system-sharded bucket the global count is ``n_sys * sys_factor``)."""
    path: str
    sys_start: int                 # first system index within the bucket
    lane_start: int                # first (shard-local) lane offset
    n_sys: int                     # shard-LOCAL DMD systems in this leaf
    flat_local: int                # real lanes per system (unpadded)
    seg_lanes: int                 # padded lanes per system (block multiple)
    shape: Tuple[int, ...]         # full global leaf shape
    local_shape: Tuple[int, ...]   # shard-local leaf shape
    stack_dims: int
    param_dtype: str
    param_spec: P
    snapshot_spec: P

    @property
    def lanes(self) -> int:
        return self.n_sys * self.seg_lanes


@dataclass(frozen=True)
class ArenaBucket:
    """One packed arena: all leaves of one (group, dtype, sharding) class."""
    key: str
    group: int
    sched: GroupSchedule
    block_n: int                   # segment quantum / kernel tile (128-mult)
    segments: Tuple[ArenaSegment, ...]
    lane_axes: Tuple[str, ...]     # mesh axes sharding the lane dim (== the
                                   # Gram psum axes; () = unsharded bucket)
    shard_factor: int              # prod of lane_axes' mesh sizes
    sys_axes: Tuple[str, ...] = () # mesh axes sharding the (leading) stack
                                   # dim — single-segment buckets only: each
                                   # device owns whole systems, the Gram
                                   # stack stays sharded over these axes
    sys_factor: int = 1            # prod of sys_axes' mesh sizes
    mesh: Optional[Mesh] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def m(self) -> int:
        return self.sched.m

    @property
    def n_sys(self) -> int:
        """Shard-LOCAL system count (what the segmented kernels see)."""
        return sum(s.n_sys for s in self.segments)

    @property
    def n_sys_global(self) -> int:
        """Global system count (the carried Gram stack's leading dim)."""
        return self.n_sys * self.sys_factor

    @property
    def n_lanes_local(self) -> int:
        return sum(s.lanes for s in self.segments)

    @property
    def n_lanes(self) -> int:
        """Global lane count (flat rows; block_n * n_blocks)."""
        return self.n_lanes_local * self.shard_factor * self.sys_factor

    @property
    def n_blocks_local(self) -> int:
        """Shard-local block count (what the segmented kernels walk)."""
        return self.n_lanes_local // self.block_n

    @property
    def n_blocks(self) -> int:
        """Global block count: leading dim of the carried block-major
        (n_blocks, m, block_n) snapshot buffer."""
        return self.n_lanes // self.block_n

    def block_sys(self) -> np.ndarray:
        """Static (shard-local) block -> system-index table for the
        segmented kernels; blocks of one system are consecutive."""
        parts = [np.repeat(
            np.arange(s.sys_start, s.sys_start + s.n_sys, dtype=np.int32),
            s.seg_lanes // self.block_n) for s in self.segments]
        return np.concatenate(parts) if parts else np.zeros(0, np.int32)

    # ---- dmd.scope (DESIGN.md §9) -----------------------------------------
    def bucket_scoped(self, scope: str) -> bool:
        """True when this bucket carries ONE shared Koopman system under
        ``scope="bucket"``. System-sharded buckets (``sys_axes``) stay
        per-system in either scope: each shard owns whole systems, and
        collapsing them into one would need a cross-shard psum over the
        stack axis that the lane-psum kernel contract does not emit."""
        if scope not in ("leaf", "bucket"):
            raise ValueError(f"unknown dmd.scope {scope!r}")
        return scope == "bucket" and not self.sys_axes

    def gram_lead(self, scope: str) -> int:
        """Leading dim of the carried Gram stack (and the bucket's share of
        the batched coefficient solve) under ``scope``."""
        return 1 if self.bucket_scoped(scope) else self.n_sys_global

    def scope_block_sys(self, scope: str) -> np.ndarray:
        """Block -> system table the kernels walk under ``scope``. Bucket
        scope collapses every block onto system 0: pad lanes are zero and
        all segments share the bucket's slot schedule, so the EXISTING
        segmented kernels then compute exactly the concatenated-bucket-state
        Gram (= the segment-SUM of the per-system Grams) in gram_row/gram,
        and broadcast the single coefficient row across every block in
        combine — the fused segment-summed reduction needs no new kernel."""
        if self.bucket_scoped(scope):
            return np.zeros(self.n_blocks_local, np.int32)
        return self.block_sys()

    def scope_n_sys(self, scope: str) -> int:
        """Shard-local system count the segmented kernels see under
        ``scope`` (their output's leading dim)."""
        return 1 if self.bucket_scoped(scope) else self.n_sys

    def lane_spec(self) -> P:
        """Spec of the FLAT 1-D lane axis (pack/unpack rows, jump blend):
        system-sharded buckets are sys-major so the flat lane dim shards
        over sys_axes THEN lane_axes."""
        from repro.kernels.arena import lane_spec
        return lane_spec(self.sys_axes + self.lane_axes)

    def buffer_spec(self) -> P:
        """Spec of the block-major (n_blocks, m, block_n) snapshot buffer:
        the same mesh axes shard the leading BLOCK axis (every shard's
        lane count is a block_n multiple, so shard boundaries are block
        boundaries and flat<->blocked reshapes split/merge the sharded
        dim divisibly)."""
        from repro.kernels.arena import buf_spec
        return buf_spec(self.sys_axes + self.lane_axes)

    def gram_spec(self) -> P:
        """Spec of the (n_sys_global, m, m) Gram stack."""
        from repro.kernels.arena import _axis_entry
        return (P(_axis_entry(self.sys_axes), None, None)
                if self.sys_axes else P())


# ---------------------------------------------------------------------------
# Bucketing
# ---------------------------------------------------------------------------

def _axes_of(entries, mesh: Optional[Mesh]) -> Tuple[str, ...]:
    """Mesh axes (size > 1) appearing in a run of PartitionSpec entries."""
    if mesh is None:
        return ()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out: List[str] = []
    for e in entries:
        if e is None:
            continue
        for a in (e if isinstance(e, tuple) else (e,)):
            if a is not None and sizes.get(a, 1) > 1 and a not in out:
                out.append(a)
    return tuple(sorted(out))


def _local_shape(plan: LeafPlan, mesh: Optional[Mesh]) -> Tuple[int, ...]:
    if mesh is None:
        return plan.shape
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ent = tuple(plan.param_spec) + (None,) * len(plan.shape)
    out = []
    for d, e in zip(plan.shape, ent):
        f = 1
        if e is not None:
            for a in (e if isinstance(e, tuple) else (e,)):
                if a is not None:
                    f *= sizes.get(a, 1)
        out.append(d // f)
    return tuple(out)


def arena_eligible(plan: LeafPlan, cfg, mesh: Optional[Mesh]) -> bool:
    """A leaf joins an arena unless it must keep its per-leaf route: only
    the forced ``dot_general`` oracle, and stack axes sharded on a
    NON-leading stack dim (shard-major packing would interleave the
    global system ordering). ``anchor=mean`` leaves pack (the full-gram
    kernel fuses the mean subtraction) and leading-dim sharded stacks get
    their own single-segment bucket (``sys_axes``)."""
    if not getattr(cfg, "arena", True):
        return False
    if plan.route == "dot_general":
        return False
    ent = tuple(plan.param_spec) + (None,) * plan.stack_dims
    if plan.stack_dims > 1 and _axes_of(ent[1:plan.stack_dims], mesh):
        return False                   # non-leading sharded stack axes
    return True


def build_arenas(plans: PyTree, cfg, mesh: Optional[Mesh] = None
                 ) -> Dict[str, ArenaBucket]:
    """LeafPlan pytree -> {bucket_key: ArenaBucket}, leaves in pytree order.

    Bucket key = (schedule group, param dtype, lane-sharding axes): one
    slot schedule (group fixes m/phase), one cast-back dtype, one psum
    pattern per bucket. ``block_n`` is the bucket-wide segment quantum:
    ``lane_block(cfg.arena_block_n, widest member)`` so tiny-leaf buckets
    collapse to one 128-lane tile while big buckets keep wide tiles."""
    from repro.kernels.ops import lane_block

    grouped: Dict[str, List[Tuple[LeafPlan, Tuple[str, ...],
                                  Tuple[str, ...]]]] = {}
    for plan in plan_entries(plans):
        if not arena_eligible(plan, cfg, mesh):
            continue
        ent = tuple(plan.param_spec) + (None,) * len(plan.shape)
        lane_axes = _axes_of(ent[plan.stack_dims:], mesh)
        sys_axes = _axes_of(ent[:plan.stack_dims], mesh)
        key = f"g{plan.group}-{plan.dtype}"
        if lane_axes:
            key += "-" + "+".join(lane_axes)
        if sys_axes:
            # system-sharded leaves get their own SINGLE-segment bucket:
            # packing two leaves shard-major would interleave their global
            # system ordering; the path disambiguates the key.
            key += ("-sys" + "+".join(sys_axes) + "-"
                    + plan.path.replace("/", "."))
        grouped.setdefault(key, []).append((plan, lane_axes, sys_axes))

    sizes = (dict(zip(mesh.axis_names, mesh.devices.shape))
             if mesh is not None else {})
    out: Dict[str, ArenaBucket] = {}
    for key in sorted(grouped):
        members = grouped[key]
        locals_ = [_local_shape(p, mesh) for p, _, _ in members]
        flats = [int(np.prod(ls[p.stack_dims:], dtype=np.int64) or 1)
                 for (p, _, _), ls in zip(members, locals_)]
        block_n = lane_block(int(getattr(cfg, "arena_block_n", 512)),
                             max(flats))
        segs: List[ArenaSegment] = []
        sys_i = lane_i = 0
        for (plan, lane_axes, sys_axes), lshape, flat in zip(
                members, locals_, flats):
            n_sys = int(np.prod(lshape[:plan.stack_dims], dtype=np.int64)) \
                if plan.stack_dims else 1
            seg_lanes = -(-flat // block_n) * block_n
            segs.append(ArenaSegment(
                path=plan.path, sys_start=sys_i, lane_start=lane_i,
                n_sys=n_sys, flat_local=flat, seg_lanes=seg_lanes,
                shape=plan.shape, local_shape=lshape,
                stack_dims=plan.stack_dims, param_dtype=plan.dtype,
                param_spec=plan.param_spec,
                snapshot_spec=plan.snapshot_spec))
            sys_i += n_sys
            lane_i += n_sys * seg_lanes
        lane_axes, sys_axes = members[0][1], members[0][2]
        factor = sys_f = 1
        for a in lane_axes:
            factor *= sizes.get(a, 1)
        for a in sys_axes:
            sys_f *= sizes.get(a, 1)
        out[key] = ArenaBucket(
            key=key, group=members[0][0].group, sched=members[0][0].sched,
            block_n=block_n, segments=tuple(segs), lane_axes=lane_axes,
            shard_factor=factor, sys_axes=sys_axes, sys_factor=sys_f,
            mesh=mesh)
    return out


def arena_paths(table: Dict[str, ArenaBucket]) -> frozenset:
    return frozenset(s.path for b in table.values() for s in b.segments)


def layout_table(table: Dict[str, ArenaBucket],
                 scope: str = "leaf") -> list:
    """JSON-able rows of the packed-arena layout — the static-audit export
    consumed by ``repro.audit`` (arena-layout pass) and the AUDIT_*.json
    artifact: one dict per bucket carrying the offset/length table the
    segmented kernels index by. ``scope`` stamps each bucket's effective
    DMD granularity and solve share (``n_solve = gram_lead(scope)``)."""
    out = []
    for key in sorted(table):
        b = table[key]
        out.append({
            "key": b.key, "group": b.group, "m": b.m,
            "scope": "bucket" if b.bucket_scoped(scope) else "leaf",
            "n_solve": b.gram_lead(scope),
            "block_n": b.block_n, "n_sys": b.n_sys,
            "n_sys_global": b.n_sys_global,
            "n_lanes_local": b.n_lanes_local, "n_lanes": b.n_lanes,
            "lane_axes": list(b.lane_axes), "shard_factor": b.shard_factor,
            "sys_axes": list(b.sys_axes), "sys_factor": b.sys_factor,
            "segments": [{
                "path": s.path, "sys_start": s.sys_start,
                "lane_start": s.lane_start, "n_sys": s.n_sys,
                "flat_local": s.flat_local, "seg_lanes": s.seg_lanes,
                "shape": list(s.shape), "local_shape": list(s.local_shape),
                "stack_dims": s.stack_dims, "param_dtype": s.param_dtype,
            } for s in b.segments],
        })
    return out


# ---------------------------------------------------------------------------
# State: the {"__arena__": ..., "leaf": ...} wrapper
# ---------------------------------------------------------------------------

def is_arena_state(x) -> bool:
    return isinstance(x, dict) and ARENA_KEY in x


def make_state(arenas: Dict[str, jnp.ndarray], leaf: PyTree) -> PyTree:
    return {ARENA_KEY: arenas, "leaf": leaf}


def split_state(x) -> Tuple[Dict[str, jnp.ndarray], PyTree]:
    return x[ARENA_KEY], x["leaf"]


def init_arena_buffers(table: Dict[str, ArenaBucket], cfg,
                       abstract: bool = False) -> Dict[str, Any]:
    from repro.kernels.arena import snapshot_rows

    dtype = jnp.dtype(cfg.snapshot_dtype)
    out = {}
    for key, b in table.items():
        shape = (b.n_blocks, snapshot_rows(b.m, dtype), b.block_n)
        out[key] = (jax.ShapeDtypeStruct(shape, dtype) if abstract
                    else jnp.zeros(shape, dtype))
    return out


def init_arena_grams(table: Dict[str, ArenaBucket], scope: str = "leaf",
                     abstract: bool = False) -> Dict[str, Any]:
    """Per-bucket Gram stacks: (n_sys_global, m, m) in leaf scope, the
    single (1, m, m) shared-operator Gram in bucket scope (DESIGN.md §9)."""
    out = {}
    for key, b in table.items():
        shape = (b.gram_lead(scope), b.m, b.m)
        out[key] = (jax.ShapeDtypeStruct(shape, jnp.float32) if abstract
                    else jnp.zeros(shape, jnp.float32))
    return out


# ---------------------------------------------------------------------------
# Pack / unpack (the gather/scatter copies; shard-local for sharded buckets)
# ---------------------------------------------------------------------------

def _pack_leaf_local(x: jnp.ndarray, seg: ArenaSegment, dtype,
                     lead: int = 0) -> jnp.ndarray:
    """(lead..., stack..., rest_local...) -> (lead..., n_sys * seg_lanes)."""
    head = x.shape[:lead]
    x = x.astype(dtype).reshape(head + (seg.n_sys, seg.flat_local))
    if seg.seg_lanes != seg.flat_local:
        pad = [(0, 0)] * lead + [(0, 0), (0, seg.seg_lanes - seg.flat_local)]
        x = jnp.pad(x, pad)
    return x.reshape(head + (seg.n_sys * seg.seg_lanes,))


def _unpack_leaf_local(row: jnp.ndarray, seg: ArenaSegment,
                       lead: int = 0) -> jnp.ndarray:
    """(lead..., N_local) -> (lead..., *local_shape) (caller casts)."""
    head = row.shape[:lead]
    x = jax.lax.slice_in_dim(row, seg.lane_start,
                             seg.lane_start + seg.lanes, axis=lead)
    x = x.reshape(head + (seg.n_sys, seg.seg_lanes))
    x = jax.lax.slice_in_dim(x, 0, seg.flat_local, axis=lead + 1)
    return x.reshape(head + seg.local_shape)


def _shard_wrap(bucket: ArenaBucket, fn, in_specs, out_specs):
    """One shard_map contract for pack/unpack AND the kernels: delegate to
    kernels/arena.py's shard_wrap so the two paths can never diverge."""
    from repro.kernels.arena import shard_wrap
    return shard_wrap(bucket.mesh, bucket.sys_axes + bucket.lane_axes, fn,
                      in_specs, out_specs)


def _params_by_path(params: PyTree) -> Dict[str, Any]:
    from repro.distributed.sharding import normalize_path
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {normalize_path(jax.tree_util.keystr(kp)): leaf
            for kp, leaf in flat}


def pack_row(bucket: ArenaBucket, params_by_path: Dict[str, Any],
             dtype) -> jnp.ndarray:
    """Current params -> one (N,) arena row (the `record` gather)."""
    leaves = [params_by_path[s.path] for s in bucket.segments]

    def local(*ls):
        return jnp.concatenate(
            [_pack_leaf_local(x, s, dtype)
             for x, s in zip(ls, bucket.segments)])

    in_specs = tuple(s.param_spec for s in bucket.segments)
    return _shard_wrap(bucket, local, in_specs, bucket.lane_spec())(*leaves)


def _unpack_row(bucket: ArenaBucket, row: jnp.ndarray, lead: int = 0
                ) -> List[jnp.ndarray]:
    """One (lead..., N) arena slab -> per-leaf local arrays (uncast)."""

    def local(r):
        return tuple(_unpack_leaf_local(r, s, lead) for s in bucket.segments)

    spec = P(*((None,) * lead + tuple(bucket.lane_spec())))
    if lead:
        out_specs = tuple(P(*((None,) * lead + tuple(s.param_spec)))
                          for s in bucket.segments)
    else:
        out_specs = tuple(s.param_spec for s in bucket.segments)
    return list(_shard_wrap(bucket, local, (spec,), out_specs)(row))


# ---------------------------------------------------------------------------
# Parameter residency (dmd.arena_native): params/moments live in the bucket
# ---------------------------------------------------------------------------

def pack_rows(table: Dict[str, ArenaBucket], tree: PyTree
              ) -> Dict[str, jnp.ndarray]:
    """{bucket: (N,) flat row} of a params-shaped ``tree``'s packed leaves;
    each row keeps the tree's OWN leaf dtype (param dtype for params, fp32
    for optimizer moments)."""
    by_path = _params_by_path(tree)
    return {key: pack_row(table[key], by_path,
                          by_path[table[key].segments[0].path].dtype)
            for key in sorted(table)}


def unpack_rows(table: Dict[str, ArenaBucket],
                arenas: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Inverse of ``pack_rows``: {leaf path: per-leaf array} (uncast: the
    row dtype is the leaf dtype)."""
    by_path: Dict[str, jnp.ndarray] = {}
    for key, row in arenas.items():
        b = table[key]
        by_path.update(zip((seg.path for seg in b.segments),
                           _unpack_row(b, row)))
    return by_path


def tree_resident(table: Dict[str, ArenaBucket], tree: PyTree,
                  rows: Optional[Dict[str, jnp.ndarray]] = None) -> PyTree:
    """Move every packed leaf of a params-shaped ``tree`` into its bucket's
    contiguous ``(N,)`` flat buffer (the resident layout); packed
    positions of the ``leaf`` subtree become None. ``rows`` are the
    tree's ``pack_rows``, when the caller built them already. Inverse:
    ``tree_leafwise``. Off the hot path — ``Trainer.fit`` entry only."""
    from repro.distributed.sharding import normalize_path

    packed = arena_paths(table)

    def strip(kp, leaf):
        path = normalize_path(jax.tree_util.keystr(kp))
        return None if path in packed else leaf

    return make_state(rows if rows is not None else pack_rows(table, tree),
                      jax.tree_util.tree_map_with_path(strip, tree))


def tree_leafwise(table: Dict[str, ArenaBucket], wrapper: PyTree,
                  by_path: Optional[Dict[str, jnp.ndarray]] = None
                  ) -> PyTree:
    """Resident wrapper -> per-leaf pytree. ALSO the in-trace zero-copy
    view expansion for the model's forward: each leaf is a static
    slice + reshape of the contiguous resident row (no data movement, no
    scatter — XLA keeps them as views), so grads of loss∘views transpose
    to pure pad-extended slices of the flat gradient. ``by_path`` is the
    wrapper's ``unpack_rows``, when the caller built it already."""
    from repro.distributed.sharding import normalize_path

    arenas, leaf = split_state(wrapper)
    if by_path is None:
        by_path = unpack_rows(table, arenas)

    def fill(kp, x):
        return by_path.get(normalize_path(jax.tree_util.keystr(kp)), x)

    return jax.tree_util.tree_map_with_path(
        fill, leaf, is_leaf=lambda x: x is None)


# ---------------------------------------------------------------------------
# record / streaming-Gram update (one launch per bucket)
# ---------------------------------------------------------------------------

def _bucket_slot(bucket: ArenaBucket, slot):
    return slot[bucket.group] if getattr(slot, "ndim", 0) == 1 else slot


def record(arenas: Dict[str, jnp.ndarray], params: PyTree, slot,
           table: Dict[str, ArenaBucket], cfg,
           group: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Write current params into each bucket's snapshot row `slot` — with
    RESIDENT params (``params`` is the arena wrapper) this is one
    ``astype`` + blocked reshape + ``dynamic_update_slice`` on the middle
    (snapshot) axis per bucket: the row is already contiguous in the
    resident buffer, and the flat->(nb, bn) reshape is a free divisible
    split. Leafwise params pay the PR-5 pack gather instead. Slot
    semantics match snapshots.record."""
    resident = is_arena_state(params)
    pres = split_state(params)[0] if resident else None
    by_path = None if resident else _params_by_path(params)
    dtype = jnp.dtype(cfg.snapshot_dtype)
    out = dict(arenas)
    for key, buf in arenas.items():
        b = table[key]
        if group is not None and b.group != group:
            continue
        s = _bucket_slot(b, slot)
        si = _static_int(s)
        if si is not None:
            if si < 0:
                continue
            s = si
        else:
            s = jnp.maximum(s, 0)
        row = (pres[key].astype(dtype) if resident
               else pack_row(b, by_path, dtype))
        out[key] = jax.lax.dynamic_update_index_in_dim(
            buf, row.reshape(b.n_blocks, b.block_n), s, axis=1)
    return out


def update_grams(agrams: Dict[str, jnp.ndarray],
                 arenas: Dict[str, jnp.ndarray], slot, cfg,
                 table: Dict[str, ArenaBucket],
                 group: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Streaming-Gram maintenance over whole buckets: ONE segmented
    gram_row launch per bucket emits every system's row, then one masked
    row+column write per bucket (set_gram_row batches over systems). The
    just-written arena row doubles as the rhs, so no second pack pass.

    Under ``cfg.scope="bucket"`` the same launch runs with the collapsed
    block table (``scope_block_sys``): the kernel's in-place segment
    accumulation then sums every block's partial into ONE (m,) row — the
    fused segment-summed reduction that writes the (m, m) bucket Gram
    directly instead of n_sys per-system Grams."""
    from repro.kernels import arena as ka

    scope = getattr(cfg, "scope", "leaf")
    out = dict(agrams)
    for key, g in agrams.items():
        b = table[key]
        if group is not None and b.group != group:
            continue
        s = _bucket_slot(b, slot)
        si = _static_int(s)
        if si is not None and si < 0:
            continue
        sv = si if si is not None else jnp.maximum(s, 0)
        buf = arenas[key]
        q = jax.lax.dynamic_index_in_dim(buf, sv, 1, keepdims=False)
        row = ka.gram_row(buf, q, b.scope_block_sys(scope),
                          b.scope_n_sys(scope),
                          anchor_first=cfg.anchor == "first",
                          block_n=b.block_n, m=b.m, mesh=b.mesh,
                          lane_axes=b.lane_axes, sys_axes=b.sys_axes)
        out[key] = dmd_math.set_gram_row(g, row, sv)
    return out


# ---------------------------------------------------------------------------
# The jump: one batched solve per group, one combine launch per bucket
# ---------------------------------------------------------------------------

def jump(cfg, table: Dict[str, ArenaBucket], params: PyTree,
         arenas: Dict[str, jnp.ndarray],
         agrams: Optional[Dict[str, jnp.ndarray]], relax,
         groups: Optional[frozenset] = None, s_vec=None,
         resident: bool = False, ridge_vec=None
         ) -> Tuple[Dict[str, jnp.ndarray], List[jnp.ndarray]]:
    """DMD jump over every arena'd leaf of the jumping groups.

    Returns ({path: new_leaf (param dtype)}, [per-leaf mean rank ...]);
    with ``resident=True`` the updates stay flat and are keyed by BUCKET
    ({bucket_key: (N,) new resident row}) — no unpack scatter at all.
    Per group: concatenate the buckets' (n_sys, m, m) Grams, ONE
    dmd_coefficients call (the batched eigh/host-eig solve — m is uniform
    within a group), split the coefficient rows back per bucket, ONE
    segmented combine launch per bucket, then scatter the flat result into
    per-leaf arrays. Missing/None ``agrams`` entries trigger the one-launch
    full Gram recompute (the streaming_gram=False A/B path — also the only
    Gram path for ``anchor=mean`` buckets, whose mean subtraction is fused
    into the kernel).

    Under ``cfg.scope="bucket"`` (DESIGN.md §9) each bucket contributes ONE
    shared-operator system to the group's batched solve (gram_lead == 1):
    the solve batch shrinks from n_leaves to n_buckets (eig host-callback
    rows shrink identically), and the combine broadcasts the bucket's
    single coefficient row across all its blocks via the collapsed
    ``scope_block_sys`` table."""
    from repro.kernels import arena as ka

    scope = getattr(cfg, "scope", "leaf")
    by_path = None if resident else _params_by_path(params)
    per_group = getattr(relax, "ndim", 0) == 1
    updates: Dict[str, jnp.ndarray] = {}
    ranks: List[jnp.ndarray] = []
    by_gi: Dict[int, List[ArenaBucket]] = {}
    for key in sorted(table):
        by_gi.setdefault(table[key].group, []).append(table[key])
    # every bucket must have its arena: a missing key would otherwise leave
    # that bucket's leaves silently unjumped (their `leaf` entries are
    # None); the indexing below fails loudly instead

    for gi in sorted(by_gi):
        if groups is not None and gi not in groups:
            continue
        buckets = by_gi[gi]
        # profiler scopes: dmd_jump/solve and dmd_jump/combine under
        # dmd_step
        with jax.named_scope("solve"):
            grams = []
            for b in buckets:
                g = agrams.get(b.key) if agrams is not None else None
                if g is None:
                    g = ka.gram(arenas[b.key], b.scope_block_sys(scope),
                                b.scope_n_sys(scope),
                                anchor_first=cfg.anchor == "first",
                                anchor_mean=cfg.anchor == "mean",
                                block_n=b.block_n, m=b.m, mesh=b.mesh,
                                lane_axes=b.lane_axes, sys_axes=b.sys_axes)
                grams.append(g)
            gcat = grams[0] if len(grams) == 1 else jnp.concatenate(grams)
            sched = buckets[0].sched
            r = relax[gi] if per_group else relax
            sd = None if s_vec is None else s_vec[gi]
            rd = None if ridge_vec is None else ridge_vec[gi]
            c, info = dmd_math.dmd_coefficients(
                gcat, s=sched.s, tol=cfg.tol, mode=cfg.mode,
                clamp_eigs=cfg.clamp_eigs, anchor=cfg.anchor,
                affine=cfg.affine, trust_region=cfg.trust_region, relax=r,
                energy=sched.energy, s_dyn=sd, atol=getattr(cfg, "atol", 0.0),
                ridge=getattr(sched, "ridge", 0.0), ridge_dyn=rd)
        ofs = 0
        for b in buckets:
            lead = b.gram_lead(scope)
            cb = jax.lax.slice_in_dim(c, ofs, ofs + lead, axis=0)
            rb = jax.lax.slice_in_dim(info["rank"], ofs, ofs + lead, axis=0)
            ofs += lead

            def seg_rank(seg, b=b, rb=rb):
                # bucket scope: one shared operator — every segment reports
                # the bucket's single rank
                if b.bucket_scoped(scope):
                    return jnp.mean(rb.astype(jnp.float32))
                return jnp.mean(jax.lax.slice_in_dim(
                    rb, seg.sys_start * b.sys_factor,
                    (seg.sys_start + seg.n_sys) * b.sys_factor, axis=0
                ).astype(jnp.float32))

            buf = arenas[b.key]
            with jax.named_scope("combine"):
                flat = ka.combine(buf, cb, b.scope_block_sys(scope),
                                  block_n=b.block_n, mesh=b.mesh,
                                  lane_axes=b.lane_axes,
                                  sys_axes=b.sys_axes)
                # Same last line of defense as the per-leaf route: a
                # non-finite BUFFER poisons the combine even under
                # c = e_last (0*inf=NaN); never leave params less finite
                # than the last snapshot.
                flat = jnp.where(jnp.isfinite(flat), flat,
                                 buf[:, b.m - 1, :].reshape(-1).astype(
                                     flat.dtype))
            if resident:
                updates[b.key] = flat.astype(
                    jnp.dtype(b.segments[0].param_dtype))
                for seg in b.segments:
                    ranks.append(seg_rank(seg))
                continue
            for seg, leaf in zip(b.segments, _unpack_row(b, flat)):
                p = by_path[seg.path]
                updates[seg.path] = leaf.astype(p.dtype)
                ranks.append(seg_rank(seg))
    return updates, ranks


# ---------------------------------------------------------------------------
# Leaf-wise views (checkpoint format compatibility)
# ---------------------------------------------------------------------------

def buffers_leafwise(table: Dict[str, ArenaBucket],
                     arenas: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
    """{path: (m, *shape) buffer} — the per-leaf layout a non-arena run
    would carry, sliced out of the arenas (checkpoint save path). The
    block-major buffer is re-slabbed to snapshot-major (m, N) first — a
    transpose + divisible reshape, off the hot path."""
    out = {}
    for key, buf in arenas.items():
        b = table[key]
        slab = jnp.transpose(buf[:, :b.m], (1, 0, 2)).reshape(b.m, b.n_lanes)
        for seg, arr in zip(b.segments, _unpack_row(b, slab, lead=1)):
            out[seg.path] = arr
    return out


def grams_leafwise(table: Dict[str, ArenaBucket],
                   agrams: Dict[str, jnp.ndarray], cfg=None,
                   arenas: Optional[Dict[str, jnp.ndarray]] = None
                   ) -> Dict[str, Any]:
    """{path: (stack..., m, m) Gram} per arena'd leaf (checkpoint save).

    The on-disk format is ALWAYS leaf-wise, in both scopes. A bucket-scoped
    (1, m, m) summed Gram cannot be split back per leaf, so those buckets
    recompute the per-system Gram stack from the snapshot buffers (one
    segmented ``ka.gram`` launch per bucket, off the hot path) and slice
    that — ``grams_from_leafwise`` sums it back to the identical bucket
    Gram on a bucket-scope restore (pad lanes are zero, segments share the
    slot schedule, so sum-of-per-system == concatenated-state exactly).
    Mid-window anchor="first" rows recomputed against the CURRENT anchor
    may differ from streamed values that used the then-current anchor —
    the same staleness class snapshots.recompute_grams already repairs on
    restore. ``cfg`` + ``arenas`` are only needed when a bucket is
    bucket-scoped (leaf-scope callers may omit them)."""
    from repro.kernels import arena as ka

    scope = getattr(cfg, "scope", "leaf") if cfg is not None else "leaf"
    out = {}
    for key, g in agrams.items():
        b = table[key]
        if b.bucket_scoped(scope):
            if arenas is None or cfg is None:
                raise ValueError(
                    "bucket-scoped Grams need the snapshot buffers to "
                    "rebuild the leaf-wise checkpoint form — pass cfg and "
                    "arenas")
            g = ka.gram(arenas[key], b.block_sys(), b.n_sys,
                        anchor_first=cfg.anchor == "first",
                        anchor_mean=cfg.anchor == "mean",
                        block_n=b.block_n, m=b.m, mesh=b.mesh,
                        lane_axes=b.lane_axes, sys_axes=b.sys_axes)
        for seg in b.segments:
            sub = jax.lax.slice_in_dim(
                g, seg.sys_start * b.sys_factor,
                (seg.sys_start + seg.n_sys) * b.sys_factor, axis=0)
            stack = seg.shape[:seg.stack_dims]
            out[seg.path] = sub.reshape(stack + (b.m, b.m))
    return out


def buffers_from_leafwise(table: Dict[str, ArenaBucket],
                          by_path: Dict[str, Any], cfg
                          ) -> Dict[str, jnp.ndarray]:
    """Inverse of buffers_leafwise: re-pack restored per-leaf buffers into
    block-major arenas (checkpoint restore path; pad lanes re-zeroed).
    The shard-local pack concatenates to snapshot-major (m, N_local) then
    re-slabs to (nb_local, m, bn) — a transpose + divisible reshape, off
    the hot path — and zero-pads to the stored snapshot_rows."""
    from repro.kernels.arena import snapshot_rows

    dtype = jnp.dtype(cfg.snapshot_dtype)
    out = {}
    for key, b in table.items():
        leaves = [by_path[s.path] for s in b.segments]
        rows = snapshot_rows(b.m, dtype)

        def local(*ls, b=b, rows=rows):
            packed = jnp.concatenate(
                [_pack_leaf_local(x, s, dtype, lead=1)
                 for x, s in zip(ls, b.segments)], axis=1)
            blocked = jnp.transpose(
                packed.reshape(b.m, -1, b.block_n), (1, 0, 2))
            return jnp.pad(blocked, ((0, 0), (0, rows - b.m), (0, 0)))

        in_specs = tuple(s.snapshot_spec for s in b.segments)
        out[key] = _shard_wrap(b, local, in_specs, b.buffer_spec())(*leaves)
    return out


def grams_from_leafwise(table: Dict[str, ArenaBucket],
                        by_path: Dict[str, Any], scope: str = "leaf"
                        ) -> Dict[str, jnp.ndarray]:
    """Inverse of grams_leafwise. Bucket-scoped buckets SUM the restored
    per-system Grams into the (1, m, m) shared-operator Gram — an exact
    identity (zero pads, shared slot schedule), so leaf-scope checkpoints
    restore into bucket scope and vice versa, remapped meshes included."""
    out = {}
    for key, b in table.items():
        parts = [jnp.asarray(by_path[s.path], jnp.float32
                             ).reshape(s.n_sys * b.sys_factor, b.m, b.m)
                 for s in b.segments]
        g = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        if b.bucket_scoped(scope):
            g = jnp.sum(g, axis=0, keepdims=True)
        out[key] = g
    return out
