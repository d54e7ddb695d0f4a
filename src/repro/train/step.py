"""Jitted train / DMD steps.

train_step(state, batch, step):
  * microbatch gradient accumulation via lax.scan (per-arch grad_accum,
    resolved against the mesh so each microbatch keeps >= 1 row per batch
    shard),
  * fp32 gradient accumulators,
  * fused DMD snapshot recording, driven by the STEP INDEX: the per-group
    slot vector is computed in-trace (schedule.slots_for_step) and each
    schedule group gets its own lax.cond, so a group in warmup/phase/
    cooldown costs nothing while another group records (DESIGN.md §4). With
    dmd.streaming_gram the O(m*n) Gram row update rides in the same
    per-group cond, against params that are already resident from the
    optimizer update. The row pass is kernel-routed per leaf by the
    accelerator's LeafPlan table (DESIGN.md §3): Pallas for flat leaves,
    shard_map'd Pallas for stacked/sharded ones.
  * optional int8-compressed cross-pod gradient sync (distributed/gradsync).

dmd_step(state, relax, groups=None): the paper's jump, masked to the
schedule group(s) whose window closed (`groups` is a STATIC tuple — the
Trainer jits it as a static argname, so a staggered schedule compiles one
small program per jumping group instead of one whole-tree spike). With the
streaming Gram carried in TrainState it is pure O(m^3) coefficient algebra
+ one combine pass per jumped leaf; without it (the
cfg.streaming_gram=False A/B baseline) it recomputes the full O(m^2*n)
Gram. Both steps share the same accelerator instance (hence the same plan
table) — pass `acc=` to avoid rebuilding it.

Arena-native residency (dmd.arena_native, DESIGN.md §7): ``Trainer.fit``
converts the TrainState at entry via ``state_resident`` — packed leaves'
params and elementwise optimizer moments move INTO their bucket's
contiguous flat buffer (the ``{"__arena__": ..., "leaf": ...}`` wrapper,
core/arena.py) — and back via ``state_unresident`` before returning. The
step fns here are layout-driven: when the params are resident, the
model's forward sees zero-copy per-leaf VIEWS (static slice + reshape of
the flat buffer, expanded in-trace by ``arena.tree_leafwise``), the
optimizer update runs directly on the flat buffers (grads of loss∘views
transpose to pad-extended slices — pad lanes stay zero), and `record`
degenerates to one dynamic_update_slice per bucket. Residency only
engages for optimizers whose moment updates are elementwise
(``RESIDENT_OPTIMIZERS``): adafactor factors trailing dims and adam8bit
quantizes fixed 256-blocks, both of which read shape structure a flat
buffer destroys.

Donation contract (audited: tests/test_donation.py inspects the compiled
HLO's input_output_alias table): under the Trainer's
``jax.jit(..., donate_argnums=(0,))`` every snapshot buffer and Gram leaf
— per-leaf AND packed-arena — aliases input to output with ZERO
buffer-sized copies, in the fused train step and in BOTH dmd_step
variants. The gated (controller) step additionally aliases the whole
TrainState: the rollback branch passes the donated pre-jump params and
moments straight through. Callers that re-use a state after the call must
clone it or rethread the returned state (see the controller bench's
gate-overhead fix in benchmarks/paper_benches.py).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import arena as arena_mod
from repro.core import leafplan, schedule as sched_mod
from repro.core import snapshots as snap
from repro.core.accelerator import DMDAccelerator, _none_like, jump_tree
from repro.distributed.sharding import constrain
from repro.optim import apply_updates, make_optimizer
from repro.train.state import TrainState

PyTree = Any


def resolve_grad_accum(acfg, mesh, global_batch: int) -> int:
    """Largest accum factor <= config that keeps >=1 row per batch shard."""
    ga = max(acfg.parallel.grad_accum, 1)
    shards = 1
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        shards = sizes.get("data", 1) * sizes.get("pod", 1)
    while ga > 1 and (global_batch // ga) % shards != 0:
        ga //= 2
    return max(min(ga, global_batch // shards), 1)


# Optimizers whose update is elementwise over each moment entry — the only
# ones whose moments can live in a flat arena buffer without changing the
# math. adafactor (factored trailing dims) and adam8bit (256-block absmax
# quantization) both read shape structure that flattening destroys.
RESIDENT_OPTIMIZERS = ("sgd", "momentum", "adam", "adamw")


def resident_enabled(acc: DMDAccelerator, acfg) -> bool:
    """Arena-native parameter residency gate (DESIGN.md §7): arenas on,
    cfg.dmd.arena_native on, and an elementwise-moment optimizer."""
    return (acc.arena_on
            and bool(getattr(acc.cfg, "arena_native", True))
            and acfg.optimizer.name in RESIDENT_OPTIMIZERS)


def state_resident(acc: DMDAccelerator, acfg, state):
    """Leafwise TrainState -> the arena-resident layout (params and
    params-shaped optimizer-moment fields packed into the bucket buffers).
    No-op when residency is gated off, nothing is packed, or the state is
    already resident. Off the hot path — Trainer.fit entry only."""
    if state is None or not resident_enabled(acc, acfg) \
            or arena_mod.is_arena_state(state.params):
        return state
    table = acc.arena_for(state.params)
    if not table:
        return state
    pdef = jax.tree_util.tree_structure(state.params)
    opt_state = state.opt_state
    # params-shaped moment trees pack; anything else (scalar counters,
    # empty states) passes through untouched
    whole = jax.tree_util.tree_structure(opt_state) == pdef      # momentum
    fields = (opt_state,) if whole else \
        tuple(opt_state) if isinstance(opt_state, tuple) else ()  # NamedTuple
    shaped = [jax.tree_util.tree_structure(f) == pdef for f in fields]
    rows = iter(_pack_rows(acc, state.params, [state.params] + [
        f for f, ok in zip(fields, shaped) if ok]))

    def res(tree):
        return arena_mod.tree_resident(table, tree, next(rows))

    params = res(state.params)
    if whole:
        opt_state = res(opt_state)
    elif any(shaped):
        opt_state = type(opt_state)(*(res(f) if ok else f
                                      for f, ok in zip(fields, shaped)))
    return state._replace(params=params, opt_state=opt_state)


def state_unresident(acc: DMDAccelerator, state):
    """Inverse of state_resident: expand resident params / moments back to
    the per-leaf layout. DMD buffers and Grams keep their packed arena
    layout (they are packed whenever arenas are on, residency or not);
    use acc.state_leafwise for the full checkpoint expansion."""
    if state is None or not arena_mod.is_arena_state(state.params):
        return state
    table = acc.arena_for(state.params)
    wrappers = [state.params] + [
        x for x in jax.tree_util.tree_leaves(
            state.opt_state, is_leaf=arena_mod.is_arena_state)
        if arena_mod.is_arena_state(x)]
    leaves = iter(_unpack_rows(acc, state.params, [
        arena_mod.split_state(w)[0] for w in wrappers]))

    def unwrap(x):
        return (arena_mod.tree_leafwise(table, x, next(leaves))
                if arena_mod.is_arena_state(x) else x)

    return state._replace(
        params=unwrap(state.params),
        opt_state=jax.tree_util.tree_map(
            unwrap, state.opt_state, is_leaf=arena_mod.is_arena_state))


# The layout conversions run as one program per accelerator and state
# signature; run eagerly under a mesh, every bucket's shard_map would
# compile again on each call. Only the moved rows and leaves go through
# the program: a leaf it merely passed through would come back a copy.
@functools.partial(jax.jit, static_argnums=0)
def _pack_rows(acc: DMDAccelerator, params, trees):
    table = acc.arena_for(params)
    return [arena_mod.pack_rows(table, t) for t in trees]


@functools.partial(jax.jit, static_argnums=0)
def _unpack_rows(acc: DMDAccelerator, params, arenas):
    table = acc.arena_for(params)
    return [arena_mod.unpack_rows(table, a) for a in arenas]


def _accelerator_for(model, acfg, mesh, acc: Optional[DMDAccelerator]
                     ) -> DMDAccelerator:
    """Shared accelerator (and hence LeafPlan table) for the step builders:
    use the caller's, or build one wired to the model's structural stack-dim
    annotation."""
    if acc is not None:
        return acc
    sd = None
    if model is not None and hasattr(model, "param_stack_dims"):
        sd = model.param_stack_dims()
    return DMDAccelerator(acfg.dmd, mesh=mesh, stack_dims=sd)


def make_train_step(model, acfg, *, mesh=None, global_batch=None,
                    loss_fn: Callable = None, donate: bool = True,
                    acc: Optional[DMDAccelerator] = None):
    """Returns train_step(state, batch, step) -> (state, metrics).

    `step` is the (traced) optimizer-step index — the per-group DMD slot
    vector is derived from it in-trace, replacing the old single `dmd_slot`
    scalar (which could only express one global window)."""
    opt = make_optimizer(acfg.optimizer)
    gb = global_batch or acfg.train.global_batch
    ga = resolve_grad_accum(acfg, mesh, gb)
    dmd_on = acfg.dmd.enabled
    acc = _accelerator_for(model, acfg, mesh, acc)
    streaming_on = acc.streaming
    _loss = loss_fn or (lambda p, b: model.loss(p, b)[0])

    def train_step(state: TrainState, batch: PyTree, step) -> tuple:
        params = state.params
        # Arena-RESIDENT params (dmd.arena_native): the model's forward
        # sees zero-copy per-leaf views of the flat bucket buffers —
        # static slice + reshape, expanded in-trace. Grads of loss∘views
        # transpose to pad-extended slices of the flat cotangent, so the
        # optimizer update below runs directly on the flat buffers.
        resident = arena_mod.is_arena_state(params)
        table = acc.arena_for(params) if resident else None

        # Profiler scopes (metadata only): the forward shows in the HLO
        # op_name as jvp(forward), the backward as transpose(jvp(forward)).
        def one_loss(p, mb):
            with jax.named_scope("forward"):
                if resident:
                    with jax.named_scope("arena_views"):
                        p = arena_mod.tree_leafwise(table, p)
                return _loss(p, mb)

        if ga > 1:
            def reshape_mb(x):
                return x.reshape((ga, x.shape[0] // ga) + x.shape[1:])
            mbs = jax.tree_util.tree_map(reshape_mb, batch)
            mbs = jax.tree_util.tree_map(
                lambda x: constrain(x, None, "batch"), mbs)

            def mb_step(carry, mb):
                gsum, lsum = carry
                l, g = jax.value_and_grad(one_loss)(params, mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (gsum, lsum + l), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(mb_step, (g0, 0.0), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / ga, gsum)
            loss = lsum / ga
        else:
            loss, grads = jax.value_and_grad(one_loss)(params, batch)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)

        with jax.named_scope("optimizer"):
            if acfg.parallel.grad_compression == "int8" \
                    and mesh is not None and "pod" in mesh.axis_names:
                from repro.distributed.gradsync import int8_psum_grads
                grads = int8_psum_grads(grads, mesh)
            updates, opt_state = opt.update(grads, state.opt_state, params,
                                            state.step)
            params = apply_updates(params, updates)

        buffers, grams = state.dmd_buffers, state.dmd_gram
        if dmd_on and buffers is not None:
            streaming = streaming_on and grams is not None
            plans = acc.plans_for(params)       # trace-time, cached
            table = acc.arena_for(params)       # {} when arenas are off
            # per-leaf snapshot/Gram calls only see the non-packed leaves;
            # with resident params that is the wrapper's leaf subtree
            # (None at every packed path — compile-time pass-throughs)
            p_leaf = (arena_mod.split_state(params)[1] if resident
                      else params)

            # One cond per schedule group: group gi's leaves are written
            # only while gi records (its slot >= 0); other groups' leaves
            # are compile-time pass-throughs inside the branch, so XLA
            # sees the same single-cond program as before for one group.
            # Arena'd leaves ride the packed route (one gather + one row
            # update + one segmented Gram launch per bucket); the per-leaf
            # code below only sees the leaves the arena could not take.
            def write(args, gi):
                bufs, g = args
                slot = jnp.maximum(slots[gi], 0)
                if arena_mod.is_arena_state(bufs):
                    arenas, leaf = arena_mod.split_state(bufs)
                    arenas = arena_mod.record(arenas, params, slot, table,
                                              acfg.dmd, group=gi)
                    leaf = snap.record(leaf, p_leaf, slot, plans, group=gi)
                    bufs = arena_mod.make_state(arenas, leaf)
                    if streaming:
                        ag, lg = arena_mod.split_state(g)
                        with jax.named_scope("gram_row"):
                            g = arena_mod.make_state(
                                arena_mod.update_grams(ag, arenas, slot,
                                                       acfg.dmd, table,
                                                       group=gi),
                                snap.update_grams(lg, leaf, p_leaf, slot,
                                                  acfg.dmd, plans, group=gi))
                    return bufs, g
                bufs = snap.record(bufs, params, slot, plans, group=gi)
                if streaming:
                    with jax.named_scope("gram_row"):
                        g = snap.update_grams(g, bufs, params, slot,
                                              acfg.dmd, plans, group=gi)
                return bufs, g

            with jax.named_scope("dmd_record"):
                slots = sched_mod.slots_for_step(acc.groups, step)
                for gi in range(len(acc.groups)):
                    buffers, grams = jax.lax.cond(
                        slots[gi] >= 0, functools.partial(write, gi=gi),
                        lambda a: a, (buffers, grams))

        new_state = TrainState(params, opt_state, state.step + 1, buffers,
                               grams, state.controller)
        return new_state, {"loss": loss}

    return train_step


def reset_opt_state_after_jump(opt, opt_state, params, plans, groups,
                               n_groups, arena=None):
    """Post-jump optimizer-moment reset.

    `groups` is the set of group indices whose moments should reset
    (callers filter by each group's ``reset_opt`` flag —
    DMDAccelerator.reset_groups). When that covers every group this is the
    legacy full ``opt.init`` — bit-exact with the pre-refactor behavior.
    Otherwise (staggered schedule, or reset-exempt groups), reset ONLY
    those groups' leaves' entries in each params-shaped field of the
    optimizer state: a staggered jump must not clobber the moments the
    other groups are accumulating mid-window. Fields that do not mirror
    the param pytree (scalar counters, empty states) are kept as-is in the
    masked case.

    With arena-RESIDENT moments the masking unit is the BUCKET, not the
    leaf: a bucket's key embeds its schedule group (core/arena.py), so
    every segment of ``arena[key]`` belongs to the same group and a
    whole-buffer swap for ``group in gset`` buckets is exactly the
    group-masked reset — a leaf-granularity mask over the flat buffer
    would either clobber other groups' segments or miss its own. `arena`
    (the accelerator's bucket table) is required when the state is
    resident; callers pass ``arena=acc.arena_for(params)``.
    """
    if groups is None or len(frozenset(groups)) >= n_groups:
        return opt.init(params)
    fresh = opt.init(params)
    pdef = jax.tree_util.tree_structure(params)
    gset = frozenset(int(g) for g in groups)

    def merge_leaf(old_field, new_field):
        return jax.tree_util.tree_map(
            lambda plan, o, n: n if (plan is not None and plan.group in gset)
            else o,
            plans, old_field, new_field, is_leaf=leafplan.is_plan_leaf)

    def merge(old_field, new_field):
        if arena_mod.is_arena_state(old_field):
            if arena is None:
                raise ValueError(
                    "resident optimizer state but no bucket table — pass "
                    "arena=acc.arena_for(params)")
            ares_o, leaf_o = arena_mod.split_state(old_field)
            ares_n, leaf_n = arena_mod.split_state(new_field)
            ares = {k: (ares_n[k] if arena[k].group in gset else v)
                    for k, v in ares_o.items()}
            return arena_mod.make_state(ares, merge_leaf(leaf_o, leaf_n))
        if jax.tree_util.tree_structure(old_field) != pdef:
            return old_field
        return merge_leaf(old_field, new_field)

    if arena_mod.is_arena_state(opt_state) \
            or jax.tree_util.tree_structure(opt_state) == pdef:
        return merge(opt_state, fresh)            # momentum-style state
    if isinstance(opt_state, tuple):              # NamedTuple of field trees
        return type(opt_state)(*(merge(o, n)
                                 for o, n in zip(opt_state, fresh)))
    return opt_state


def audit_step_fns(model, acfg, *, mesh=None,
                   acc: Optional[DMDAccelerator] = None,
                   loss_fn: Callable = None, donate: bool = True):
    """The static-audit surface (repro.audit.targets): every jitted hot
    entry point, under the Trainer's EXACT jit contract (same
    donate_argnums, same static argnames), plus the shared accelerator.

    Returns ``(acc, {name: jitted_fn})`` with
      * ``train_step``     — the fused step (record+Gram riding inside),
      * ``dmd_step``       — the jump in whichever variant the config
                             selects (plain or loss-gated controller),
      * ``record_update``  — record + streaming-Gram maintenance as a
                             standalone program (buffers AND grams
                             donated), so the data-pass invariants are
                             auditable in isolation from the model's
                             forward/backward.

    ``donate=False`` drops every donate_argnums — the seeded-violation
    fixture the donation pass must catch (audit ``--mutate
    drop-donation`` and the CI mutation test)."""
    acc = _accelerator_for(model, acfg, mesh, acc)
    dn = (0,) if donate else ()
    fns = {
        "train_step": jax.jit(
            make_train_step(model, acfg, mesh=mesh, loss_fn=loss_fn,
                            acc=acc), donate_argnums=dn),
        "dmd_step": jax.jit(
            make_dmd_step(acfg, mesh=mesh, acc=acc, model=model,
                          loss_fn=loss_fn), donate_argnums=dn,
            static_argnames=("groups",)),
    }

    def record_update(buffers, grams, params, slots):
        return acc.record(buffers, params, slots, grams)

    fns["record_update"] = jax.jit(record_update,
                                   donate_argnums=(0, 1) if donate else ())
    return acc, fns


def make_dmd_step(acfg, *, mesh=None, acc: Optional[DMDAccelerator] = None,
                  model=None, loss_fn: Callable = None):
    """Returns the paper's jump as a jittable step. Two variants:

      * controller OFF (default): dmd_step(state, relax, groups=None) —
        the ungated jump, VERBATIM the pre-controller path (bit-exact;
        pinned by the fused-step oracle in tests/test_trainer.py).
      * controller ON (cfg.controller.enabled): dmd_step(state, relax,
        eval_batch, groups=None) — the loss-gated jump
        (core/controller.py, DESIGN.md §5): one candidate jump at the
        controller's adapted per-group horizon (ridge-shrunk by the
        meta-tuned per-group ridge when meta_lr > 0), then an in-trace
        gate on the `eval_batch` loss — the caller must pass a VALIDATION
        batch disjoint from the training stream (train/loop.py carves
        one). Accept / shrinkage line search over cfg.controller
        .shrink_levels (re-blends of the same solved jump — no extra
        solves) / reject with bit-exact rollback (pre-jump params and
        moments pass through untouched; buffers, Gram, and the schedule's
        cooldown arithmetic were never disturbed). With meta_lr > 0 a
        final backward through the jump meta-tunes relax_eff/ridge_eff
        (core/controller.py::meta_update). Needs `model` or `loss_fn` for
        the gate forwards.

    `groups` is a STATIC tuple of schedule-group indices to jump (the
    Trainer passes acc.apply_groups(step) and jits it as a static argname);
    None jumps every group — the legacy single-window call. `relax` is a
    scalar or the per-group vector from acc.relax_vector.
    """
    cfg = acfg.dmd
    opt = make_optimizer(acfg.optimizer)
    acc = _accelerator_for(model, acfg, mesh, acc)
    streaming_on = acc.streaming

    if not acc.controller_on:
        def dmd_step(state: TrainState, relax,
                     groups: Optional[Sequence[int]] = None) -> tuple:
            if state.dmd_buffers is None:
                return state, {"mean_rank": jnp.zeros((), jnp.float32)}
            grams = state.dmd_gram
            if grams is None or not streaming_on:
                grams = _none_like(state.dmd_buffers)
            plans = acc.plans_for(state.params)
            with jax.named_scope("dmd_jump"):
                params, mean_rank = jump_tree(
                    cfg, plans, state.params, state.dmd_buffers, grams,
                    relax, groups=groups, arena=acc.arena_for(state.params))
                opt_state = state.opt_state
                # the jump teleports the jumped groups' weights; reset
                # those groups' moments — unless the group opts out
                # (sched.reset_opt)
                reset = acc.reset_groups(groups)
                if reset:
                    opt_state = reset_opt_state_after_jump(
                        opt, state.opt_state, params, plans, reset,
                        acc.n_groups, arena=acc.arena_for(params))
            new_state = TrainState(params, opt_state, state.step,
                                   state.dmd_buffers, state.dmd_gram,
                                   state.controller)
            return new_state, {"mean_rank": mean_rank}

        return dmd_step

    # ---- loss-gated controller variant ------------------------------------
    from repro.core import controller as ctrl_mod

    ccfg = cfg.controller
    if loss_fn is None and model is None:
        raise ValueError("controller mode needs `model` or `loss_fn` for "
                         "the gate's held-out-loss forwards")
    _loss = loss_fn or (lambda p, b: model.loss(p, b)[0])
    levels = tuple(float(f) for f in
                   (getattr(ccfg, "shrink_levels", (0.5,)) or (0.5,)))
    for f in levels:
        if not 0.0 < f < 1.0:
            raise ValueError(f"controller shrink_levels must lie in (0, 1): "
                             f"got {levels}")
    # Meta-tuning differentiates THROUGH the jump: matpow is plain traced
    # linear algebra, but eig mode routes the operator power through a host
    # pure_callback with no JVP.
    meta_on = float(getattr(ccfg, "meta_lr", 0.0)) > 0
    if meta_on and cfg.mode != "matpow":
        raise ValueError("controller meta-tuning (meta_lr > 0) needs "
                         "dmd.mode='matpow' — the eig host callback is not "
                         "differentiable")

    def gated_dmd_step(state: TrainState, relax, eval_batch,
                       groups: Optional[Sequence[int]] = None) -> tuple:
        with jax.named_scope("dmd_jump"):
            return gated_jump(state, relax, eval_batch, groups)

    def gated_jump(state, relax, eval_batch, groups):
        zero = jnp.zeros((), jnp.float32)
        if state.dmd_buffers is None:
            return state, {"mean_rank": zero, "ctrl_outcome":
                           jnp.zeros((), jnp.int32), "ctrl_loss_pre": zero,
                           "ctrl_loss_jump": zero, "ctrl_loss_kept": zero,
                           "ctrl_gain": zero, "ctrl_level": zero}
        grams = state.dmd_gram
        if grams is None or not streaming_on:
            grams = _none_like(state.dmd_buffers)
        plans = acc.plans_for(state.params)
        ctrl = state.controller
        jumped = tuple(range(acc.n_groups)) if groups is None \
            else tuple(groups)
        # resident params: the gate forwards see per-leaf views, same
        # in-trace expansion as the fused train step's one_loss
        resident = arena_mod.is_arena_state(state.params)
        table = acc.arena_for(state.params) if resident else None

        def eval_loss(p):
            with jax.named_scope("gate"):
                if resident:
                    p = arena_mod.tree_leafwise(table, p)
                return _loss(p, eval_batch)

        # Candidate jump at the adapted horizon, relax tempered by the
        # per-group effective scale. `relax` may be scalar or (n_groups,);
        # the product with relax_eff is always the per-group vector. The
        # meta-tuned ridge_eff only feeds the solve while meta-tuning is on
        # (meta_lr > 0) — with it off the schedule's STATIC per-group ridge
        # applies and the trace is unchanged from the pre-ridge path.
        s_vec = ctrl_mod.effective_s(ctrl, acc.groups, ccfg)
        relax_vec = jnp.broadcast_to(
            jnp.asarray(relax, jnp.float32),
            (acc.n_groups,)) * ctrl.relax_eff
        ridge_vec = ctrl.ridge_eff if meta_on else None
        table_full = acc.arena_for(state.params)
        p_jump, mean_rank = jump_tree(cfg, plans, state.params,
                                      state.dmd_buffers, grams, relax_vec,
                                      groups=groups, s_vec=s_vec,
                                      arena=table_full, ridge_vec=ridge_vec)

        loss_pre = eval_loss(state.params)
        loss_post = eval_loss(p_jump)

        reset = acc.reset_groups(groups)

        def reset_moments(params):
            if not reset:
                return state.opt_state
            return reset_opt_state_after_jump(
                opt, state.opt_state, params, plans, reset, acc.n_groups,
                arena=acc.arena_for(params))

        def accept_full(_):
            return p_jump, reset_moments(p_jump), \
                jnp.asarray(ctrl_mod.ACCEPT, jnp.int32), loss_post, \
                jnp.float32(levels[0])

        def blend(f):
            # relax enters the coefficients linearly, so the blend
            # f*w_jump + (1-f)*w_pre IS the f-scaled-relax jump — no second
            # coefficient solve, one extra gate forward per tried rung
            # (paid only inside its branch).
            return jax.tree_util.tree_map(
                lambda a, b: ((1.0 - f) * a.astype(jnp.float32)
                              + f * b.astype(jnp.float32)).astype(a.dtype),
                state.params, p_jump)

        def reject(_):
            # Bit-exact rollback: the donated pre-jump params and
            # moments pass straight through; buffers / Gram / schedule
            # cooldown were never touched by the jump.
            return state.params, state.opt_state, \
                jnp.asarray(ctrl_mod.REJECT, jnp.int32), loss_pre, \
                jnp.float32(levels[0])

        def try_levels(idx):
            # Shrinkage line search (DESIGN.md §5): nested conds over the
            # static shrink_levels ladder — each rung re-blends the SAME
            # solved jump at a smaller fraction and keeps the first one the
            # gate accepts; falling off the ladder is the rollback. The
            # default single rung (0.5,) is the legacy blind halving.
            if idx >= len(levels):
                return reject
            f = levels[idx]

            def attempt(_):
                p_lvl = blend(f)
                loss_lvl = eval_loss(p_lvl)

                def accept_lvl(_):
                    return p_lvl, reset_moments(p_lvl), \
                        jnp.asarray(ctrl_mod.SCALED, jnp.int32), loss_lvl, \
                        jnp.float32(f)

                return jax.lax.cond(
                    ctrl_mod.gate_outcome(loss_pre, loss_lvl,
                                          ccfg.accept_tol),
                    accept_lvl, try_levels(idx + 1), None)

            return attempt

        with jax.named_scope("gate"):
            params, opt_state, outcome, loss_final, level = jax.lax.cond(
                ctrl_mod.gate_outcome(loss_pre, loss_post, ccfg.accept_tol),
                accept_full, try_levels(0), None)

        gain = (loss_pre - loss_final) / jnp.maximum(loss_pre, 1e-30)
        new_ctrl = ctrl_mod.update_on_jump(ctrl, jumped, outcome, gain,
                                           ccfg, acc.groups, level=level)
        if meta_on:
            # Weiner & Semaan meta-tuning: the gate loss differentiated
            # THROUGH the jump wrt a per-group relax scale (at 1) and the
            # ridge knob; meta_update EMAs relax_eff/ridge_eff toward the
            # descent direction. One extra backward per gate round — the
            # Gram, eigh, and buffers are all shared with the candidate.
            def meta_loss(knobs):
                rscale, rknob = knobs
                pv, _ = jump_tree(cfg, plans, state.params,
                                  state.dmd_buffers, grams,
                                  relax_vec * rscale, groups=groups,
                                  s_vec=s_vec, arena=table_full,
                                  ridge_vec=rknob)
                return eval_loss(pv)

            g_relax, g_ridge = jax.grad(meta_loss)(
                (jnp.ones((acc.n_groups,), jnp.float32), ctrl.ridge_eff))
            new_ctrl = ctrl_mod.meta_update(new_ctrl, jumped, g_relax,
                                            g_ridge, ccfg, acc.groups)
        new_state = TrainState(params, opt_state, state.step,
                               state.dmd_buffers, state.dmd_gram, new_ctrl)
        # telemetry: `ctrl_loss_jump` is the FULL candidate's eval loss,
        # `ctrl_loss_kept` the loss of whatever was kept (== loss_jump on
        # accept, the winning blend's loss on a scale-back, loss_pre on a
        # rollback), `ctrl_level` the realized line-search fraction — gain
        # is computed from `kept`, so the trio is always self-consistent.
        return new_state, {"mean_rank": mean_rank, "ctrl_outcome": outcome,
                           "ctrl_loss_pre": loss_pre,
                           "ctrl_loss_jump": loss_post,
                           "ctrl_loss_kept": loss_final, "ctrl_gain": gain,
                           "ctrl_level": level}

    return gated_dmd_step
