"""DMDAccelerator: the paper's Algorithm 1 as a training-loop component.

Usage (see repro.train.loop for full integration):

    acc = DMDAccelerator(cfg.dmd, mesh=mesh,
                         stack_dims=model.param_stack_dims())
    buffers = acc.init(params)               # also builds the LeafPlan table
    grams = acc.init_grams(buffers)          # streaming-Gram state (or None)
    # every optimizer step (record always returns the (buffers, grams)
    # pair; grams stays None when not streaming). acc.slots(step) is the
    # per-group slot vector — groups not recording (slot < 0) are skipped:
    buffers, grams = acc.record(buffers, params, acc.slots(step), grams)
    if acc.should_apply(step):               # some group's window closed
        params, stats = acc.apply(params, buffers, grams=grams, step=step)

Per-leaf scheduling (core/schedule.py, DESIGN.md §4): the schedule is a
TABLE of groups — group 0 is the DMDConfig globals, further groups come
from cfg.groups rules resolved per leaf at plan-build time. slot /
should_record / should_apply / round_index are per-group queries
(`group=` arg, default 0); `slots(step)` / `apply_groups(step)` /
`relax_vector(step)` are the whole-table views the Trainer and the fused
train step consume. Groups with distinct `phase` offsets jump on different
steps, so at most a subset of leaves pays the jump at any step.

`record` is fused into the jitted train step by the trainer; `apply` is its
own jitted program (runs every m steps). Both operate on the whole param
pytree at once — XLA fuses the per-layer DMD updates, realizing the paper's
"easily parallelized across layers" note as a single SPMD program.

LeafPlan registry (core/leafplan.py, DESIGN.md §3): every per-leaf routing
decision — leading stack axes, kernel route (``pallas_flat`` |
``pallas_shard_map`` | ``dot_general``), buffer/Gram PartitionSpecs, n-tile —
is computed ONCE per leaf from the real param pytree + mesh + the model's
structural `param_stack_dims()` annotation, and carried as a pytree of frozen
`LeafPlan` records aligned 1:1 with params/buffers/grams. `plans_for(params)`
builds (and caches, keyed by structure+shape+dtype) the table — it reads only
shape/path metadata, so it also works at trace time inside a jitted step —
and `plan_table()` renders the audited dispatch table with the schedule
columns (group / m / phase):

    print(acc.plan_table(params))
    # path            route            group    m   s  phase energy stack arena        off ...
    # /seg0/attn/wqkv pallas_shard_map default  14  55 0     -      1     g0-bfloat16  0
    # /final_norm/... pallas_flat      norms    6   24 7     0.995  0     g1-bfloat16  4096

(`s` is the group's configured horizon — the static cap the controller's
adapted horizon lives under; `energy` shows the controller-mode
cumulative-energy rank target, "-" while the tol mask rules; `arena` /
`off` show each leaf's packed-bucket assignment and lane offset —
core/arena.py, DESIGN.md §7 — "-" for leaves kept on the per-leaf route;
`scope` shows the leaf's DMD granularity under cfg.scope — "bucket" when
its bucket fits ONE shared Koopman operator over the concatenated bucket
state, "leaf" otherwise — DESIGN.md §9.)

Bucket-scope Koopman DMD (cfg.scope="bucket", DESIGN.md §9): each arena
bucket becomes ONE DMD system — the streaming update writes the (m, m)
segment-summed bucket Gram directly (same segmented kernels, collapsed
block table), the jump solves n_buckets coefficient systems per group
instead of n_leaves (eig host-callback batches shrink identically), and
the combine broadcasts one coefficient row per bucket. `spectrum_table()`
renders the per-bucket Koopman eigenvalue magnitudes / mode decay rates
as a convergence diagnostic (comparable across scopes — leaf scope
segment-sums its Grams first). Default "leaf" is bit-exact legacy.

Packed arenas (core/arena.py, DESIGN.md §7): with cfg.arena (default on)
all compatible leaves of a schedule group are packed into contiguous
per-bucket block-major (n_blocks, m, block_n) ring buffers at init (the
layout that keeps every arena pass a batch-leading contraction and makes
the TPU tile the storage tile) — the snapshot/Gram/combine data
passes then cost ONE segmented kernel launch per bucket per step
(kernels/arena.py) and the jump ONE batched coefficient solve per group,
instead of one launch + one eigensolve per leaf. `arena_for(params)`
exposes the bucket table; `init`/`record`/`apply` transparently carry the
``{"__arena__": ..., "leaf": ...}`` two-route state. cfg.arena=False is
the per-leaf A/B oracle (bit-exact with the pre-arena route).

Arena-native residency (cfg.arena_native, DESIGN.md §7): during
``Trainer.fit`` the packed leaves' PARAMS (and elementwise optimizer
moments) also live in the bucket buffers, carried as the same wrapper
layout. Every entry point here is layout-driven — `record` turns into one
dynamic_update_slice per bucket when it sees resident params, `jump_tree`
writes flat bucket rows back without an unpack scatter, and
`state_leafwise` expands residency for checkpoints, so disk format and
non-fit callers never see the wrapper. ``arena_native=False`` keeps the
PR-5 pack-copy route as the bit-exact A/B oracle.

Streaming Gram (DESIGN.md §2): with cfg.streaming_gram the (stack..., m, m)
Gram is maintained incrementally — each record adds one O(m*n) row pass —
so `apply` skips the O(m^2*n) gram_matrix recompute entirely and runs pure
O(m^3) coefficient algebra plus one combine pass. gram_matrix remains the
correctness oracle (and the cfg.streaming_gram=False A/B baseline).

Jump controller (core/controller.py, DESIGN.md §5): with
cfg.controller.enabled the Trainer's jitted DMD step gates every jump on a
held-out microbatch loss (accept / halve-relax re-blend / bit-exact
rollback) and carries per-group ControllerState in TrainState —
`init_controller()` builds it, `controller_on` reports the mode. The
host-side `apply` below stays UNGATED (benches and examples gate by hand);
the gated path lives in train/step.py::make_dmd_step.

Static audits (repro.audit, DESIGN.md §8): every structural invariant
above — buffer/Gram donation, the sharded kernels' collective budget,
trace size, arena lane alignment, schedule phase disjointness — is
checked against the lowered jaxprs/HLO of the step fns built from this
module plus the plan/schedule/arena tables by

    PYTHONPATH=src python -m repro.audit --arch <name> [--reduced] [--mesh DxM]

which CI runs per config (nonzero exit on violation; see the pass
catalog in DESIGN.md §8).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import arena as arena_mod
from repro.core import dmd, leafplan, schedule as sched_mod
from repro.core import snapshots as snap

PyTree = Any


@dataclass
class LeafJump:
    """Result of one leaf's DMD jump. Deliberately NOT a registered pytree:
    it must survive tree_map as an opaque leaf so callers can split it with
    an isinstance check — the old (params, rank) tuples were sniffed by
    shape, which silently mis-split params pytrees containing genuine
    2-tuple nodes."""
    params: Any
    rank: Any


def dmd_leaf_jump(cfg, plan: leafplan.LeafPlan, p, buf, gram, relax,
                  s_dyn=None, ridge_dyn=None):
    """One leaf of the DMD jump: coefficients from `gram` (the carried
    streaming Gram; recomputed from the buffer when None) + one combine
    pass, both kernel-routed by the leaf's plan. The extrapolation horizon
    `s` is the leaf's GROUP horizon (plan.sched.s) — mixed-window groups
    jump different distances; in controller mode `s_dyn` (a traced scalar,
    the group's adapted horizon) replaces it, with plan.sched.s as the
    static cap, the group's energy target replaces the tol mask, and
    `ridge_dyn` (traced, the controller's meta-tuned shrinkage) overrides
    the group's static ridge. Shared by DMDAccelerator.apply and
    train.step.make_dmd_step."""
    from repro.kernels import ops, sharded

    nstack = plan.stack_dims
    anchor_first = cfg.anchor == "first"
    # profiler scopes: dmd_jump/solve and dmd_jump/combine under dmd_step
    with jax.named_scope("solve"):
        if gram is None:
            if plan.route == "pallas_shard_map" and plan.anchor_ok:
                gram = sharded.gram(buf, plan, anchor_first=anchor_first)
            elif plan.route == "pallas_flat" and plan.anchor_ok:
                gram = ops.gram(buf, anchor_first=anchor_first,
                                block_n=plan.block_n)
            else:
                gram = dmd.gram_matrix(buf, anchor=cfg.anchor,
                                       stack_dims=nstack,
                                       upcast=cfg.gram_upcast)
        s = plan.sched.s if plan.sched is not None else cfg.s
        energy = plan.sched.energy if plan.sched is not None else 0.0
        ridge = plan.sched.ridge if plan.sched is not None else 0.0
        c, info = dmd.dmd_coefficients(
            gram, s=s, tol=cfg.tol, mode=cfg.mode,
            clamp_eigs=cfg.clamp_eigs, anchor=cfg.anchor,
            affine=cfg.affine, trust_region=cfg.trust_region, relax=relax,
            energy=energy, s_dyn=s_dyn, atol=getattr(cfg, "atol", 0.0),
            ridge=ridge, ridge_dyn=ridge_dyn)
    with jax.named_scope("combine"):
        if plan.route == "pallas_shard_map":
            w = sharded.combine(buf, c, plan)
        elif plan.route == "pallas_flat":
            w = ops.combine(buf, c, block_n=plan.block_n)
        else:
            w = dmd.combine_snapshots(buf, c, stack_dims=nstack,
                                      upcast=cfg.gram_upcast)
        # Even c = e_last cannot save a non-finite BUFFER: the combine
        # contracts every row, and 0 * inf = NaN. The jump must never
        # leave params less finite than the last snapshot — fall back
        # elementwise.
        w = jnp.where(jnp.isfinite(w), w, buf[-1].astype(w.dtype))
    return w.astype(p.dtype), jnp.mean(info["rank"].astype(jnp.float32))


def jump_tree(cfg, plans: PyTree, params: PyTree, buffers: PyTree,
              grams: PyTree, relax, groups: Optional[Sequence[int]] = None,
              s_vec=None, arena=None,
              ridge_vec=None) -> Tuple[PyTree, jnp.ndarray]:
    """Whole-pytree DMD jump keyed by the plan table: returns (new_params,
    mean_rank). Excluded leaves (plan None) pass through untouched.

    `groups` (STATIC iterable of schedule-group indices) masks the jump to
    those groups' leaves — the staggered schedule jumps only the group(s)
    whose window closed, so the other groups' leaves cost nothing (they are
    compile-time pass-throughs, not runtime selects). None jumps every
    group. `relax` is a scalar or a per-group (n_groups,) vector indexed by
    ``plan.group`` (each group anneals on its own round counter). `s_vec`
    (controller mode) is a traced per-group (n_groups,) int vector of
    adapted horizons — None keeps each group's static configured s.
    `ridge_vec` (controller mode) is a traced per-group (n_groups,) float
    vector of meta-tuned ridge shrinkages — None keeps each group's static
    schedule ridge.

    `arena` (the accelerator's bucket table, core/arena.py) serves every
    arena'd leaf through the packed route: one batched coefficient solve
    per jumping group plus one segmented combine launch per bucket; the
    per-leaf tree_map below then only sees the leaves the arena could not
    take (their buffer entries in the ``leaf`` subtree are None for arena'd
    paths, so the two routes partition the tree cleanly)."""
    gset = None if groups is None else frozenset(int(g) for g in groups)
    per_group = getattr(relax, "ndim", 0) == 1

    # Arena-RESIDENT params (dmd.arena_native): split the wrapper — the
    # per-leaf route below runs over the leaf subtree (None at packed
    # paths, so packed leaves are compile-time pass-throughs there), and
    # the arena jump returns whole flat bucket rows that overlay the
    # resident buffers directly (no unpack scatter at all).
    resident = arena_mod.is_arena_state(params)
    pres: dict = {}
    if resident:
        pres, params = arena_mod.split_state(params)

    arena_updates: dict = {}
    ranks: list = []
    if arena_mod.is_arena_state(buffers):
        if not arena:
            # Refuse loudly: with `arena or {}` the packed leaves would
            # silently pass through UNJUMPED (their `leaf` entries are
            # None, so neither route would touch them).
            raise ValueError(
                "buffers are arena-packed but no bucket table was given — "
                "pass arena=acc.arena_for(params) (the accelerator that "
                "built these buffers)")
        arenas, buffers = arena_mod.split_state(buffers)
        agrams, grams = (arena_mod.split_state(grams)
                         if arena_mod.is_arena_state(grams) else (None, grams))
        arena_updates, ranks = arena_mod.jump(
            cfg, arena, params, arenas, agrams, relax, groups=gset,
            s_vec=s_vec, resident=resident, ridge_vec=ridge_vec)
        ranks = list(ranks)

    def one(plan, p, buf, g):
        if plan is None or buf is None:
            return p
        if gset is not None and plan.group not in gset:
            return p
        r = relax[plan.group] if per_group else relax
        sd = None if s_vec is None else s_vec[plan.group]
        rd = None if ridge_vec is None else ridge_vec[plan.group]
        w, rank = dmd_leaf_jump(cfg, plan, p, buf, g, r, s_dyn=sd,
                                ridge_dyn=rd)
        return LeafJump(w, rank)

    out = jax.tree_util.tree_map(one, plans, params, buffers, grams,
                                 is_leaf=leafplan.is_plan_leaf)
    is_jump = lambda x: isinstance(x, LeafJump)
    new_params = jax.tree_util.tree_map(
        lambda o: o.params if isinstance(o, LeafJump) else o, out,
        is_leaf=is_jump)
    if resident:
        new_params = arena_mod.make_state({**pres, **arena_updates},
                                          new_params)
    elif arena_updates:
        from repro.distributed.sharding import normalize_path

        def overlay(kp, p):
            return arena_updates.get(
                normalize_path(jax.tree_util.keystr(kp)), p)
        new_params = jax.tree_util.tree_map_with_path(overlay, new_params)
    ranks += [o.rank for o in jax.tree_util.tree_leaves(out, is_leaf=is_jump)
              if isinstance(o, LeafJump)]
    mean_rank = (jnp.mean(jnp.stack([r.astype(jnp.float32) for r in ranks]))
                 if ranks else jnp.zeros((), jnp.float32))
    return new_params, mean_rank


def _none_like(buffers: PyTree) -> PyTree:
    """All-None tree matching `buffers` (placeholder gram tree)."""
    return jax.tree_util.tree_map(lambda b: None, buffers,
                                  is_leaf=lambda x: x is None)


class DMDAccelerator:
    def __init__(self, cfg, *, mesh=None, stack_dims: Optional[PyTree] = None):
        """`mesh` + `stack_dims` (the model's structural
        `param_stack_dims()` pytree; None = no stacked leaves) feed the
        LeafPlan table built lazily from the first param pytree seen.
        The schedule-group table (core/schedule.py) resolves eagerly from
        the config: group 0 = the globals, one more group per non-exclude
        cfg.groups rule."""
        self.cfg = cfg
        self.mesh = mesh
        self.stack_dims = stack_dims
        self.groups = sched_mod.resolve_groups(cfg)
        self.n_groups = len(self.groups)
        self._plans = None
        self._plans_key = None
        self._arena = None
        self._apply_jit = None

    @property
    def streaming(self) -> bool:
        """Streaming-Gram engine active? (anchor="mean" has no one-pass row
        update — its anchor moves with every record — so it keeps the
        recompute path.)"""
        return (self.cfg.enabled and self.cfg.streaming_gram
                and self.cfg.anchor in ("none", "first"))

    @property
    def controller_on(self) -> bool:
        """Loss-gated jump controller active? (core/controller.py,
        DESIGN.md §5). Off = the ungated schedule, bit-exact legacy."""
        ccfg = getattr(self.cfg, "controller", None)
        return bool(self.cfg.enabled and ccfg is not None and ccfg.enabled)

    def init_controller(self, abstract: bool = False):
        """Fresh per-group ControllerState carried in TrainState (None when
        the controller is off). `abstract=True` -> ShapeDtypeStruct leaves
        (dry-run)."""
        if not self.controller_on:
            return None
        from repro.core import controller as ctrl_mod
        return ctrl_mod.init_state(self.groups, abstract=abstract)

    # ---- the per-leaf dispatch table --------------------------------------
    def plans_for(self, params: PyTree) -> PyTree:
        """LeafPlan pytree for `params`, cached by structure+shape+DTYPE.
        Dtypes are part of the key because the plan records them (and
        anchor/route decisions may consult them): a bf16<->fp32 param cast
        must rebuild the table, not silently reuse a stale one. Reads only
        metadata, so it is trace-safe (params may be tracers or
        ShapeDtypeStructs)."""
        if arena_mod.is_arena_state(params):
            # Arena-resident params (dmd.arena_native): the wrapper has no
            # leaf metadata for the packed paths — the plan table that
            # BUILT the residency layout is the only valid one.
            if self._plans is None:
                raise ValueError(
                    "resident params before plans were built — call "
                    "plans_for/init on the leafwise params first")
            return self._plans
        key = (jax.tree_util.tree_structure(params),
               tuple((tuple(l.shape), str(getattr(l, "dtype", "?")))
                     for l in jax.tree_util.tree_leaves(params)))
        if self._plans is None or self._plans_key != key:
            self._plans = leafplan.build_plans(params, self.cfg, self.mesh,
                                               self.stack_dims)
            self._plans_key = key
            self._arena = None
        return self._plans

    @property
    def scope(self) -> str:
        """The DMD system granularity (DESIGN.md §9): "leaf" (default,
        bit-exact legacy — one operator per leaf/stacked layer) or
        "bucket" (one shared Koopman operator per arena bucket; the jump's
        solve batch is n_buckets, not n_leaves)."""
        return getattr(self.cfg, "scope", "leaf")

    @property
    def arena_on(self) -> bool:
        """Packed-arena route active? (core/arena.py, DESIGN.md §7).
        Off (``dmd.arena=False``) = the per-leaf route everywhere — the
        bit-exact A/B oracle."""
        return bool(self.cfg.enabled and getattr(self.cfg, "arena", True))

    def arena_for(self, params: PyTree):
        """The bucket table ({key: ArenaBucket}) for `params` — built once
        per plan table (same cache key), empty when arenas are off or no
        leaf is eligible. Static metadata only, so trace-safe like
        plans_for."""
        self.plans_for(params)
        return self._arena_table()

    def _arena_table(self):
        """Bucket table from the CURRENT plan cache (the one builder —
        arena_for and plan_table both route here, so the audited dump and
        the running kernels can never see different bucketings)."""
        if self._plans is None:
            raise ValueError("no plans built yet — pass params")
        if self._arena is None:
            self._arena = (arena_mod.build_arenas(self._plans, self.cfg,
                                                  self.mesh)
                           if self.arena_on else {})
        return self._arena

    def plan_table(self, params: Optional[PyTree] = None) -> str:
        """Audited dispatch-table dump per selected leaf: kernel route,
        schedule group / m / s / phase / energy, stack dims, shapes, the
        packed-arena assignment (`arena` = bucket key, `off` = the leaf's
        lane offset in the bucket — "-" for per-leaf-route leaves), the
        leaf's DMD `scope` ("bucket" when its bucket fits one shared
        Koopman operator under cfg.scope — DESIGN.md §9; "leaf"
        otherwise), and the PartitionSpec / psum axes. Needs the plans
        built — pass `params` on first use."""
        if params is not None:
            self.plans_for(params)
        return leafplan.plan_table(
            self._plans, self._arena_table(),
            native=bool(getattr(self.cfg, "arena_native", True)),
            scope=self.scope)

    def spectrum_table(self, buffers: PyTree,
                       grams: Optional[PyTree] = None) -> str:
        """Per-bucket Koopman spectrum dump — the convergence diagnostic
        (DESIGN.md §9): for every arena bucket, the DMD eigenvalue
        magnitudes and per-step mode decay rates of the operator the NEXT
        jump would fit, computed host-side from the carried (or recomputed)
        Gram via core/dmd.py::dmd_eigenvalues_from_gram. ``|lambda| < 1``
        modes decay (the bucket's trajectory is settling — a candidate for
        the controller's per-group exclusion), ``~ 1`` drift, ``> 1``
        grow. In bucket scope each row is the bucket's single shared
        operator; in leaf scope the bucket's per-system Grams are
        segment-summed first (the identical operator bucket scope would
        fit), so the diagnostic is comparable across scopes. Off the hot
        path — pulls O(m^2) Grams per bucket to host."""
        import numpy as np

        from repro.kernels import arena as ka

        if self._plans is None:
            raise ValueError("spectrum_table before init: no plan table yet")
        table = self._arena_table()
        rows = [("bucket", "scope", "m", "rank", "|lam|max", "|lam|min",
                 "decay/step", "eigs")]
        agrams = (arena_mod.split_state(grams)[0]
                  if arena_mod.is_arena_state(grams) else None)
        arenas = (arena_mod.split_state(buffers)[0]
                  if arena_mod.is_arena_state(buffers) else {})
        for key in sorted(table):
            b = table[key]
            g = agrams.get(key) if agrams is not None else None
            if g is None:
                g = ka.gram(arenas[key], b.scope_block_sys(self.scope),
                            b.scope_n_sys(self.scope),
                            anchor_first=self.cfg.anchor == "first",
                            anchor_mean=self.cfg.anchor == "mean",
                            block_n=b.block_n, m=b.m, mesh=b.mesh,
                            lane_axes=b.lane_axes, sys_axes=b.sys_axes)
            # diagnostic table, not a step fn: the sync is the point
            g = np.asarray(jax.device_get(g), np.float64)  # lint: allow-host-sync
            if not b.bucket_scoped(self.scope):
                # leaf scope: sum the per-system Grams — the concatenated-
                # state operator bucket scope would fit (exact identity)
                g = g.sum(axis=0, keepdims=True)
            lam = dmd.dmd_eigenvalues_from_gram(g[0], tol=self.cfg.tol)
            mag = np.abs(lam)
            scope = "bucket" if b.bucket_scoped(self.scope) else "leaf"
            if mag.size == 0:
                rows.append((key, scope, str(b.m), "0", "-", "-", "-", "-"))
                continue
            # decay/step: slowest mode's per-step magnitude ratio — how
            # fast the bucket's dominant dynamics die out (1.0 = drift)
            top = np.sort(mag)[::-1][:4]
            rows.append((key, scope, str(b.m), str(mag.size),
                         f"{mag.max():.4f}", f"{mag.min():.4f}",
                         f"{mag.max():.4f}",
                         " ".join(f"{v:.3f}" for v in top)))
        widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
        return "\n".join("  ".join(v.ljust(w) for v, w in zip(r, widths))
                         for r in rows)

    # ---- schedule ---------------------------------------------------------
    # Per-group cycle after warmup+phase: [cooldown unrecorded steps]
    # [m recorded steps -> jump]. The math lives in core/schedule.py
    # (GroupSchedule); these are the per-group queries plus the whole-table
    # views the Trainer consumes. Single-group configs reproduce the
    # pre-refactor scalar schedule bit-exactly (group 0 == the globals).
    def slot(self, step: int, group: int = 0) -> int:
        """Buffer row for group `group`'s snapshot after optimizer step
        `step`; negative while the group is not recording (warmup / phase /
        cooldown). A group jumps when its slot m-1 is written, then its
        window restarts (paper: bp_iter = 0)."""
        return self.groups[group].slot(step)

    def slots(self, step: int) -> np.ndarray:
        """(n_groups,) per-group slot vector — the `record` write positions
        (groups with a negative entry are skipped)."""
        return sched_mod.slots_array(self.groups, step)

    def should_record(self, step: int) -> bool:
        return self.cfg.enabled and any(
            g.should_record(step) for g in self.groups)

    def should_apply(self, step: int) -> bool:
        return self.cfg.enabled and bool(self.apply_groups(step))

    def apply_groups(self, step: int) -> Tuple[int, ...]:
        """Indices of the groups whose window closes at `step` (staggered
        phases make this usually empty or a single group)."""
        if not self.cfg.enabled:
            return ()
        return tuple(i for i, g in enumerate(self.groups)
                     if g.should_apply(step))

    def round_index(self, step: int, group: int = 0) -> int:
        return self.groups[group].round_index(step)

    def relax_for_round(self, round_idx: int, group: int = 0) -> float:
        return self.groups[group].relax_for_round(round_idx)

    def relax_vector(self, step: int) -> np.ndarray:
        """(n_groups,) relax factors at `step` — each group annealed on its
        OWN round counter. Indexed by plan.group inside jump_tree."""
        return np.asarray([g.relax_for_round(g.round_index(step))
                           for g in self.groups], np.float32)

    def reset_groups(self, groups: Optional[Sequence[int]] = None
                     ) -> Tuple[int, ...]:
        """Of the jumped groups (None = all), the ones whose optimizer
        moments should reset afterwards (sched.reset_opt — slow leaf
        families typically opt out; see core/schedule.py)."""
        src = range(self.n_groups) if groups is None else groups
        return tuple(g for g in src if self.groups[g].reset_opt)

    # ---- state ------------------------------------------------------------
    def init(self, params: PyTree) -> PyTree:
        """Snapshot state for `params`. With arenas on (DESIGN.md §7) this
        is the two-route wrapper ``{"__arena__": {bucket: block-major
        (n_blocks, m, block_n) ring buffer}, "leaf": per-leaf pytree}``
        — arena'd leaves live packed,
        the rest (dot_general oracle / sharded stack axes) keep their
        per-leaf (m, *shape) buffers; otherwise the plain per-leaf pytree.
        Abstract-aware either way (ShapeDtypeStruct in -> out)."""
        if not self.cfg.enabled:
            return None
        plans = self.plans_for(params)
        table = self.arena_for(params)
        skip = arena_mod.arena_paths(table) if table else None
        leaf = snap.init_buffers(params, self.cfg, plans, skip_paths=skip)
        if not table:
            return leaf
        abstract = any(isinstance(l, jax.ShapeDtypeStruct)
                       for l in jax.tree_util.tree_leaves(params))
        return arena_mod.make_state(
            arena_mod.init_arena_buffers(table, self.cfg, abstract=abstract),
            leaf)

    def init_grams(self, buffers: PyTree) -> Optional[PyTree]:
        """Running-Gram state mirroring `buffers` (None when not streaming):
        per-bucket (n_sys, m, m) stacks for the arenas, per-leaf
        (stack..., m, m) leaves for the rest."""
        if buffers is None or not self.streaming:
            return None
        if self._plans is None:
            raise ValueError("init_grams before init: no LeafPlan table yet")
        if not arena_mod.is_arena_state(buffers):
            return snap.init_grams(buffers, self.cfg, self._plans)
        arenas, leaf = arena_mod.split_state(buffers)
        abstract = any(isinstance(l, jax.ShapeDtypeStruct)
                       for l in jax.tree_util.tree_leaves(buffers))
        return arena_mod.make_state(
            arena_mod.init_arena_grams(self._arena_table(),
                                       scope=self.scope, abstract=abstract),
            snap.init_grams(leaf, self.cfg, self._plans))

    def record(self, buffers: PyTree, params: PyTree, slot,
               grams: Optional[PyTree] = None) -> Tuple[PyTree, PyTree]:
        """Write params into each buffer's row; with `grams` also refresh
        the streaming Gram rows. `slot` is a scalar (single-group / legacy)
        or the per-group vector from ``slots(step)`` — groups with a
        negative entry are skipped. ALWAYS returns (buffers, grams) — grams
        stays None for non-streaming callers — so `buffers, grams =
        acc.record(...)` is the one idiom regardless of configuration."""
        if buffers is None:
            return None, None
        if self.n_groups > 1 and getattr(slot, "ndim", 0) != 1:
            raise ValueError(
                f"{self.n_groups} schedule groups need the per-group slot "
                "vector — pass acc.slots(step), not a scalar slot")
        plans = self.plans_for(params)
        if not arena_mod.is_arena_state(buffers):
            new_bufs = snap.record(buffers, params, slot, plans)
            if grams is None:
                return new_bufs, None
            return new_bufs, snap.update_grams(grams, new_bufs, params, slot,
                                               self.cfg, plans)
        table = self.arena_for(params)
        arenas, leaf = arena_mod.split_state(buffers)
        # With RESIDENT params (the arena wrapper) arena_mod.record is a
        # pointer bump — one astype + dynamic_update_slice per bucket; the
        # per-leaf snapshot calls below only see the non-packed leaves
        # (the wrapper's leaf subtree is None at every packed path).
        arenas = arena_mod.record(arenas, params, slot, table, self.cfg)
        p_leaf = (arena_mod.split_state(params)[1]
                  if arena_mod.is_arena_state(params) else params)
        leaf = snap.record(leaf, p_leaf, slot, plans)
        new_bufs = arena_mod.make_state(arenas, leaf)
        if grams is None:
            return new_bufs, None
        agrams, lgrams = arena_mod.split_state(grams)
        new_grams = arena_mod.make_state(
            arena_mod.update_grams(agrams, arenas, slot, self.cfg, table),
            snap.update_grams(lgrams, leaf, p_leaf, slot, self.cfg, plans))
        return new_bufs, new_grams

    # ---- checkpoint format (leaf-wise arena views) ------------------------
    def params_leafwise(self, params):
        """Param pytree with arena-resident leaves expanded back to
        per-leaf arrays — identity for non-resident params. This is the
        serving/publish template layout: the trainer's publish hook
        (train/loop.py ``on_publish``) exports through here so a serving
        ParamStore / WeightsChannel never sees the packed flat buckets."""
        if arena_mod.is_arena_state(params):
            return arena_mod.tree_leafwise(self.arena_for(params), params)
        return params

    def state_leafwise(self, state):
        """TrainState -> the same state with arenas unpacked into the
        per-leaf buffer/Gram pytrees (the ``dmd.arena=False`` layout) AND
        resident params/optimizer moments expanded back to per-leaf arrays.
        Checkpoints are ALWAYS written in this form, so they are
        byte-compatible across arena on/off AND arena_native on/off,
        pre-residency checkpoints restore unchanged, and elastic
        remapped-mesh restore keeps using the audited per-leaf
        PartitionSpecs. No-op when nothing is packed."""
        if state is None:
            return state
        if arena_mod.is_arena_state(getattr(state, "params", None)):
            table = self.arena_for(state.params)

            def unwrap(x):
                return (arena_mod.tree_leafwise(table, x)
                        if arena_mod.is_arena_state(x) else x)

            state = state._replace(
                params=self.params_leafwise(state.params),
                opt_state=jax.tree_util.tree_map(
                    unwrap, state.opt_state,
                    is_leaf=arena_mod.is_arena_state))
        if not arena_mod.is_arena_state(state.dmd_buffers):
            return state
        from repro.distributed.sharding import normalize_path
        table = self.arena_for(state.params)
        arenas, leaf = arena_mod.split_state(state.dmd_buffers)
        by_path = arena_mod.buffers_leafwise(table, arenas)

        def fill(from_paths):
            def one(kp, x):
                return from_paths.get(
                    normalize_path(jax.tree_util.keystr(kp)), x)
            return one

        bufs = jax.tree_util.tree_map_with_path(
            fill(by_path), leaf, is_leaf=lambda x: x is None)
        grams = state.dmd_gram
        if arena_mod.is_arena_state(grams):
            agrams, lgrams = arena_mod.split_state(grams)
            # bucket scope: the (1, m, m) summed Grams cannot split per
            # leaf — grams_leafwise recomputes the per-system stacks from
            # the snapshot buffers, keeping the disk format leaf-wise
            g_by_path = arena_mod.grams_leafwise(table, agrams,
                                                 cfg=self.cfg, arenas=arenas)
            grams = jax.tree_util.tree_map_with_path(
                fill(g_by_path), lgrams, is_leaf=lambda x: x is None)
        return state._replace(dmd_buffers=bufs, dmd_gram=grams)

    def state_arenaize(self, state):
        """Inverse of state_leafwise: re-pack a restored per-leaf state
        into the arena layout this accelerator runs with (no-op when
        arenas are off / empty / already packed)."""
        if state is None or state.dmd_buffers is None \
                or arena_mod.is_arena_state(state.dmd_buffers) \
                or not self.arena_on:
            return state
        table = self.arena_for(state.params)
        if not table:
            return state
        from repro.distributed.sharding import normalize_path
        paths = arena_mod.arena_paths(table)

        def by_path_of(tree):
            flat = jax.tree_util.tree_flatten_with_path(
                tree, is_leaf=lambda x: x is None)[0]
            return {normalize_path(jax.tree_util.keystr(kp)): leaf
                    for kp, leaf in flat}

        def strip(tree):
            return jax.tree_util.tree_map_with_path(
                lambda kp, x: None
                if normalize_path(jax.tree_util.keystr(kp)) in paths else x,
                tree, is_leaf=lambda x: x is None)

        bufs = arena_mod.make_state(
            arena_mod.buffers_from_leafwise(table, by_path_of(
                state.dmd_buffers), self.cfg), strip(state.dmd_buffers))
        grams = state.dmd_gram
        if grams is not None and self.streaming:
            grams = arena_mod.make_state(
                arena_mod.grams_from_leafwise(table, by_path_of(grams),
                                              scope=self.scope),
                strip(grams))
        return state._replace(dmd_buffers=bufs, dmd_gram=grams)

    # ---- the DMD jump -----------------------------------------------------
    def _apply_impl(self, params: PyTree, buffers: PyTree, grams: PyTree,
                    relax: jnp.ndarray, groups=None) -> Tuple[PyTree, dict]:
        plans = self.plans_for(params)
        new_params, mean_rank = jump_tree(self.cfg, plans, params, buffers,
                                          grams, relax, groups=groups,
                                          arena=self.arena_for(params))
        return new_params, {"mean_rank": mean_rank}

    def apply(self, params: PyTree, buffers: PyTree,
              round_idx: int = 0, grams: Optional[PyTree] = None,
              groups: Optional[Tuple[int, ...]] = None,
              step: Optional[int] = None) -> Tuple[PyTree, dict]:
        """The jump. Two idioms:

          * ``apply(params, buffers, round_idx, grams=...)`` — legacy:
            every group jumps, relaxed at `round_idx` (per-group anneal).
          * ``apply(params, buffers, grams=..., step=step)`` — schedule-
            driven: only ``apply_groups(step)`` jump, each at its own
            round's relax. `groups` (static tuple) overrides the mask.
        """
        if buffers is None:
            return params, {}
        if grams is None or not self.streaming:
            grams = _none_like(buffers)
        self.plans_for(params)        # build outside the trace for caching
        if step is not None:
            if groups is None:
                groups = self.apply_groups(step)
            relax = jnp.asarray(self.relax_vector(step), jnp.float32)
        else:
            relax = jnp.asarray(
                [self.relax_for_round(round_idx, g)
                 for g in range(self.n_groups)], jnp.float32)
        groups = None if groups is None else tuple(sorted(groups))
        if self._apply_jit is None:
            self._apply_jit = jax.jit(self._apply_impl, donate_argnums=(0,),
                                      static_argnames=("groups",))
        return self._apply_jit(params, buffers, grams, relax, groups=groups)
