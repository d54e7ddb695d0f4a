"""Audit-target construction: one config -> the traced/compiled programs
and static tables every pass runs over.

For an ``--arch`` (plus ``--reduced`` / ``--mesh``) this builds the SAME
jitted entry points the Trainer runs — via the introspection hook
``train/step.py::audit_step_fns`` (same donate_argnums, same static
argnames) — and traces + compiles each once:

  * ``train_step``       — the fused step (record + streaming Gram inside),
  * ``dmd_step``         — the plain (ungated) jump, every group,
  * ``dmd_step_gated``   — the loss-gated controller variant (built from a
                           controller-enabled clone of the config),
  * ``record_update``    — record + Gram maintenance standalone (buffers
                           and Grams donated), so the data-pass invariants
                           are auditable in isolation.

plus the static tables: the LeafPlan pytree, the ArenaBucket table, and
the resolved GroupSchedule table (their ``*_records`` export hooks feed
the AUDIT_*.json artifact directly).

``mutate=`` applies a named seeded violation (repro.audit.mutations) so
tests and the CI mutation lane can prove each pass bites.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, FrozenSet, Optional, Tuple

PyTree = Any

# Model-shrink overrides shared with the tier-1 audits
# (tests/test_donation.py, tests/test_trace_size.py): the audit CLI and the
# test suite must lower the SAME reduced programs or their pins diverge.
REDUCED_OVERRIDES = dict(n_layers=2, d_model=32, d_ff=64, vocab_size=128,
                         n_heads=2, n_kv_heads=1, head_dim=16)
REDUCED_BATCH, REDUCED_SEQ = 4, 16

# pollutant-mlp --reduced: a same-family softsign MLP small enough for the
# CI fast lane (the full paper sizes stay the default).
REDUCED_MLP_SIZES = (6, 16, 32, 40)


@dataclass(frozen=True)
class AuditTarget:
    """One traced+compiled program under audit."""
    name: str
    jaxpr: Any                      # ClosedJaxpr of the traced call
    hlo: str                        # compiled HLO text
    donated: bool                   # donate_argnums applied at jit time
    n_state_leaves: int             # leaves of the donated arg (arg 0)
    n_dmd_leaves: int               # buffer+gram leaves within it
    buffer_shapes: FrozenSet[str]   # HLO shape strings (audit.hlo)
    gram_shapes: FrozenSet[str]


@dataclass
class AuditContext:
    arch: str
    reduced: bool
    mesh_shape: Optional[Tuple[int, ...]]
    mutate: Optional[str]
    acfg: Any
    acc: Any                        # DMDAccelerator (plans/arena built)
    mesh: Any
    plans: PyTree
    arena: Dict[str, Any]           # {key: ArenaBucket}
    groups: Tuple[Any, ...]         # resolved GroupSchedule table
    state: Any                      # TrainState (shape source of truth)
    targets: Dict[str, AuditTarget] = field(default_factory=dict)
    # serve-engine build info (repro.serve.audit.attach_serve): program
    # registry counts for the serve-compile pass, or None when no serving
    # build was attached (--serve).
    serve: Optional[Dict[str, Any]] = None

    @property
    def cfg(self):
        return self.acfg.dmd

    @property
    def config_key(self) -> str:
        key = self.arch
        if self.reduced:
            key += "-reduced"
        if self.mesh_shape:
            key += "-mesh"
        return key

    def meta(self) -> Dict[str, Any]:
        return {"reduced": self.reduced,
                "mesh": ("x".join(map(str, self.mesh_shape))
                         if self.mesh_shape else None),
                "mutate": self.mutate,
                "config_key": self.config_key}

    def tables(self) -> Dict[str, Any]:
        """The static tables as JSON-able records (the export hooks)."""
        from repro.core import arena as arena_mod
        from repro.core import leafplan, schedule as sched_mod
        return {"plans": leafplan.plan_records(self.plans),
                "arena": arena_mod.layout_table(
                    self.arena, scope=getattr(self.cfg, "scope", "leaf")),
                "groups": sched_mod.schedule_records(self.groups)}


def _build_model_and_config(arch: str, reduced_flag: bool):
    """(model, acfg, example_batch) for one audit build."""
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.configs.base import OptimizerConfig, TrainConfig

    acfg = get_config(arch)
    if acfg.model.family == "mlp":
        from repro.configs.pollutant_mlp import PAPER_SIZES
        from repro.models.mlp_net import MLPModel
        sizes = REDUCED_MLP_SIZES if reduced_flag else PAPER_SIZES
        batch_rows = 8
        model = MLPModel(sizes, acfg.model.act)
        batch = {"x": jnp.zeros((batch_rows, sizes[0]), jnp.float32),
                 "y": jnp.zeros((batch_rows, sizes[-1]), jnp.float32)}
        return model, acfg, batch

    from repro.configs.base import DMDConfig
    from repro.models.transformer import LanguageModel
    if reduced_flag:
        mc = reduced(acfg.model, **REDUCED_OVERRIDES)
        acfg = dataclasses.replace(
            acfg, model=mc,
            dmd=DMDConfig(enabled=True, m=4, s=10, tol=1e-4,
                          warmup_steps=4, cooldown_steps=2,
                          arena=acfg.dmd.arena),
            optimizer=OptimizerConfig(name="adam", lr=3e-3,
                                      schedule="constant"),
            parallel=dataclasses.replace(acfg.parallel, grad_accum=1,
                                         remat="none"),
            train=TrainConfig(global_batch=REDUCED_BATCH,
                              seq_len=REDUCED_SEQ))
    mc = acfg.model
    model = LanguageModel(mc, head_tp=False if reduced_flag else None,
                          chunk_k=min(16 if reduced_flag else 1024,
                                      acfg.train.seq_len))
    b, s = acfg.train.global_batch, acfg.train.seq_len
    batch = {"tokens": jnp.zeros((b, s), jnp.int32),
             "labels": jnp.zeros((b, s), jnp.int32)}
    if mc.mrope_sections:
        batch["positions"] = jnp.zeros((b, 3, s), jnp.int32)
    return model, acfg, batch


def _init_state(model, acfg, acc, mesh=None):
    import jax
    import jax.numpy as jnp
    from repro.optim import make_optimizer
    from repro.train.state import TrainState
    from repro.train.step import state_resident

    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer(acfg.optimizer)
    bufs = acc.init(params) if acfg.dmd.enabled else None
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32),
                       bufs, acc.init_grams(bufs), acc.init_controller())
    if mesh is not None:
        # Audit the launch-path placement (launch/inputs.state_specs):
        # donated inputs arriving in their final sharding — a replicated
        # state would make the step's constrain() calls reshard donated
        # args and read as spurious copies.
        from jax.sharding import NamedSharding
        from repro.launch.inputs import state_specs
        specs = state_specs(state, mesh, plans=acc.plans_for(params),
                            arena=acc.arena_for(params))
        state = jax.tree_util.tree_map(
            lambda l, s: jax.device_put(l, NamedSharding(mesh, s)),
            state, specs)
    # Same entry conversion Trainer.fit applies (train/step.py): the audit
    # must lower the step programs over the SAME resident layout training
    # runs with, or the residency pass would audit a program that never
    # executes.
    return state_resident(acc, acfg, state)


def trace_target(name: str, jitted, args, kwargs, state,
                 donated: bool = True) -> AuditTarget:
    """Trace + compile ONE jitted entry point into an AuditTarget — the
    seam the tier-1 tests use to route their existing Trainer programs
    through the shared passes without rebuilding a full context."""
    import jax
    from repro.audit import hlo as hlo_mod

    traced = jitted.trace(*args, **kwargs)
    hlo = traced.lower().compile().as_text()
    bufs, grams, _ = hlo_mod.dmd_state_shapes(state)
    n_dmd = sum(
        1 for kp, l in jax.tree_util.tree_flatten_with_path(state)[0]
        if l is not None and any(
            k in jax.tree_util.keystr(kp)
            for k in ("dmd_buffers", "dmd_gram")))
    return AuditTarget(
        name=name, jaxpr=traced.jaxpr, hlo=hlo, donated=donated,
        n_state_leaves=len(jax.tree_util.tree_leaves(state)),
        n_dmd_leaves=n_dmd,
        buffer_shapes=frozenset(bufs), gram_shapes=frozenset(grams))


def serve_target(name: str, jitted, args, caches,
                 donated: bool = True) -> AuditTarget:
    """AuditTarget for a serving program (launch/serve.py::serve_fns):
    the KV caches play the role of the managed tensors — every cache leaf
    must alias input->output (donated arg 2) and no cache-shaped copy may
    survive compilation, exactly the donation-alias invariant the train
    programs pin on their snapshot buffers."""
    import jax
    from repro.audit import hlo as hlo_mod

    import jax.numpy as jnp

    traced = jitted.trace(*args)
    hlo = traced.lower().compile().as_text()
    leaves = [l for l in jax.tree_util.tree_leaves(caches)
              if l is not None]
    # the copy ban covers the KV tensors (floating dtypes); the s32 length
    # counters are 8-byte scalars XLA may copy freely — they still count
    # toward the alias floor (every cache leaf must be donated).
    shapes = frozenset(hlo_mod.shape_str(l) for l in leaves
                       if jnp.issubdtype(l.dtype, jnp.floating))
    return AuditTarget(
        name=name, jaxpr=traced.jaxpr, hlo=hlo, donated=donated,
        n_state_leaves=len(leaves), n_dmd_leaves=len(leaves),
        buffer_shapes=shapes, gram_shapes=frozenset())


def jaxpr_target(name: str, jaxpr, state=None) -> AuditTarget:
    """AuditTarget from a bare jaxpr (no compile): enough for the
    jaxpr-only passes (trace-budget, host-callback). ``jaxpr`` may be a
    ClosedJaxpr (jax.make_jaxpr output) or an inner Jaxpr."""
    from repro.audit import hlo as hlo_mod

    inner = getattr(jaxpr, "jaxpr", jaxpr)
    if state is not None:
        bufs, grams, _ = hlo_mod.dmd_state_shapes(state)
    else:
        bufs, grams = set(), set()
    return AuditTarget(name=name, jaxpr=inner, hlo="", donated=True,
                       n_state_leaves=0, n_dmd_leaves=0,
                       buffer_shapes=frozenset(bufs),
                       gram_shapes=frozenset(grams))


def adhoc_context(arch: str, acfg, targets: Dict[str, AuditTarget], *,
                  mesh=None, plans=None, arena=None, groups=(),
                  state=None, reduced: bool = False) -> AuditContext:
    """A partial AuditContext over caller-built targets — the tier-1
    tests wrap their existing Trainer programs in one of these and call
    the shared pass functions directly (same invariants as the CLI, no
    duplicate HLO-regex logic). ``arch`` doubles as the pin key
    (AuditContext.config_key), so a test pinning a bespoke model names it
    here and registers its ceiling in repro/audit/pins.py."""
    return AuditContext(
        arch=arch, reduced=reduced, mesh_shape=None, mutate=None,
        acfg=acfg, acc=None, mesh=mesh, plans=plans,
        arena=dict(arena or {}), groups=tuple(groups), state=state,
        targets=dict(targets))


def build_context(arch: str, *, reduced: bool = False,
                  mesh_shape: Optional[Tuple[int, ...]] = None,
                  mutate: Optional[str] = None,
                  serve: bool = False) -> AuditContext:
    """Build every audit target + static table for one config.

    ``mesh_shape`` (e.g. ``(2, 4)``) traces under a real mesh — the
    process must already expose enough devices (the CLI sets
    ``--xla_force_host_platform_device_count`` before importing jax).

    ``serve=True`` (CLI ``--serve``) additionally builds a reduced
    serving engine over the same model family, drives a warmup + steady
    workload through it, and attaches its program-registry counts
    (``ctx.serve``) and compiled decode program (``serve_decode`` target)
    for the serve-compile pass."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.audit import mutations as mut_mod
    from repro.configs.base import DMDControllerConfig
    from repro.distributed.sharding import mesh_context
    from repro.launch.mesh import make_mesh
    from repro.train.step import audit_step_fns

    mutation = mut_mod.get(mutate) if mutate else None

    model, acfg, batch = _build_model_and_config(arch, reduced)
    if mutation is not None and mutation.config is not None:
        acfg = mutation.config(acfg)
    donate = mutation.donate if mutation is not None else True

    mesh = None
    cm = contextlib.nullcontext()
    if mesh_shape:
        axis_names = {1: ("model",), 2: ("data", "model"),
                      3: ("pod", "data", "model")}[len(mesh_shape)]
        mesh = make_mesh(tuple(mesh_shape), axis_names)
        cm = mesh_context(mesh)

    with cm:
        acc, fns = audit_step_fns(model, acfg, mesh=mesh, donate=donate)
        if mutation is not None and mutation.wrap_fns is not None:
            fns = mutation.wrap_fns(acc, fns, mesh)
        state = _init_state(model, acfg, acc, mesh)
        plans = acc.plans_for(state.params)
        arena = acc.arena_for(state.params)

        ctx = AuditContext(
            arch=arch, reduced=reduced,
            mesh_shape=tuple(mesh_shape) if mesh_shape else None,
            mutate=mutate, acfg=acfg, acc=acc, mesh=mesh, plans=plans,
            arena=dict(arena), groups=acc.groups, state=state)

        step = jnp.asarray(5, jnp.int32)
        relax = jnp.ones((acc.n_groups,), jnp.float32)
        ctx.targets["train_step"] = trace_target(
            "train_step", fns["train_step"], (state, batch, step), {},
            state, donate)
        ctx.targets["dmd_step"] = trace_target(
            "dmd_step", fns["dmd_step"], (state, relax),
            {"groups": None}, state, donate)
        slots = jnp.asarray(acc.slots(5))
        if state.dmd_buffers is not None:
            ctx.targets["record_update"] = trace_target(
                "record_update", fns["record_update"],
                (state.dmd_buffers, state.dmd_gram, state.params, slots),
                {}, state, donate)

        # Gated (controller) variant: a controller-enabled clone — the
        # rollback branch must thread the WHOLE donated state through.
        gated_acfg = dataclasses.replace(
            acfg, dmd=dataclasses.replace(
                acfg.dmd, controller=DMDControllerConfig(enabled=True,
                                                         eval_rows=4)))
        gacc, gfns = audit_step_fns(model, gated_acfg, mesh=mesh,
                                    donate=donate)
        if mutation is not None and mutation.wrap_fns is not None:
            gfns = mutation.wrap_fns(gacc, gfns, mesh)
        gstate = _init_state(model, gated_acfg, gacc, mesh)
        grelax = jnp.ones((gacc.n_groups,), jnp.float32)
        ctx.targets["dmd_step_gated"] = trace_target(
            "dmd_step_gated", gfns["dmd_step"], (gstate, grelax, batch),
            {"groups": None}, gstate, donate)

    # Serving build OUTSIDE the mesh context: the engine's vmapped decode
    # is a single-host program (its constrain() calls are identity with no
    # active mesh) — mesh serving placement is launch/inputs.py's
    # serve_state_specs, exercised by its own tests.
    if serve or (mutation is not None and mutation.serve):
        from repro.serve.audit import attach_serve
        attach_serve(ctx, mutate=(mutation.serve_cfg
                                  if mutation is not None else None))

    if mutation is not None and mutation.post is not None:
        mutation.post(ctx)
    return ctx
