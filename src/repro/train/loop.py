"""Host-side training loop: DMD schedule, checkpointing, fault tolerance.

The loop is deliberately thin: all math lives in jitted steps. Host-side
responsibilities:
  * the DMD schedule via DMDAccelerator: the fused train step derives every
    group's (warmup / phase / cooldown / m-window) position from the step
    index in-trace; the loop only decides WHICH groups' windows closed
    (acc.apply_groups) and dispatches the jump masked to those groups —
    with staggered phases that is at most one group's jump spike per step,
  * controller mode (dmd.controller.enabled): the dispatched jump is the
    LOSS-GATED step (accept / scale-back / bit-exact rollback on a held-out
    microbatch — core/controller.py, DESIGN.md §5); the loop only plumbs
    the eval batch, all gating happens in-trace,
  * checkpoint cadence + atomic save + resume (bit-exact, tested),
  * preemption (SIGTERM) -> save-and-exit,
  * failure injection for tests (raise at step k, resume from disk).

Determinism contract: the data iterator is a pure function of the step index
(see repro.data), so a restarted worker replays identical batches — the
straggler/elastic-restart story depends on this.
"""
from __future__ import annotations

import signal
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp

from repro.core.accelerator import DMDAccelerator
from repro.core import snapshots as snap
from repro.optim import make_optimizer
from repro.train.state import TrainState
from repro.train.step import (make_dmd_step, make_train_step,
                              state_resident, state_unresident)

PyTree = Any


class Trainer:
    def __init__(self, model, acfg, *, mesh=None, loss_fn=None,
                 checkpoint_dir: Optional[str] = None,
                 fail_at_step: Optional[int] = None,
                 val_batch: Optional[PyTree] = None,
                 on_publish: Optional[Callable] = None):
        self.model = model
        self.acfg = acfg
        self.mesh = mesh
        # Serving publish hook (DESIGN.md §10): called as
        # ``on_publish(params_leafwise, version)`` after every jump the
        # controller did NOT reject (every jump when the controller is
        # off) — the trainer side of the live weight hot-swap. The params
        # are exported leaf-wise (acc.params_leafwise), so the hook can
        # feed a ParamStore / WeightsChannel directly.
        self.on_publish = on_publish
        # One accelerator — hence ONE LeafPlan dispatch table — shared by the
        # schedule, the fused train step and the jump (DESIGN.md §3).
        self.acc = DMDAccelerator(
            acfg.dmd, mesh=mesh,
            stack_dims=(model.param_stack_dims()
                        if hasattr(model, "param_stack_dims") else None))
        self.opt = make_optimizer(acfg.optimizer)
        self.checkpoint_dir = checkpoint_dir or acfg.train.checkpoint_dir
        self.fail_at_step = fail_at_step
        self._preempted = False

        self.train_step = jax.jit(
            make_train_step(model, acfg, mesh=mesh, loss_fn=loss_fn,
                            acc=self.acc),
            donate_argnums=(0,))
        # `groups` static: each distinct jumping-group subset compiles its
        # own (small) jump program — the staggered-schedule spike killer.
        # With the controller on, the jitted jump also carries the in-trace
        # loss gate (extra eval_batch argument — train/step.py).
        self.controller_on = self.acc.controller_on
        self.dmd_step = jax.jit(make_dmd_step(acfg, mesh=mesh, acc=self.acc,
                                              model=model, loss_fn=loss_fn),
                                donate_argnums=(0,),
                                static_argnames=("groups",))
        # Persistent validation split for the jump controller's gate
        # (ISSUE 9): carved ONCE at trainer init, NEVER drawn from the
        # training iterator — a gate scored on training rows consumes a
        # training batch (shifting the stream) and happily accepts
        # train-overfit jumps. Callers may hand in their own split; token
        # models get a deterministic carve from the reserved validation
        # stream fold (repro.data.tokens.validation_batch).
        self.val_batch = None
        if self.controller_on:
            self.val_batch = (val_batch if val_batch is not None
                              else self._carve_val_batch())

    def _publish(self, state, dmd_info, version: int) -> None:
        """Fire the serving publish hook for a non-rejected jump. The
        controller's REJECT branch restored the pre-jump state bit-exactly
        (publishing it would be a no-op swap); ACCEPT and SCALED both
        changed the weights being served, so both publish. With the
        controller off every jump publishes."""
        if self.controller_on:
            from repro.core import controller as ctrl_mod
            outcome = dmd_info.get("ctrl_outcome")
            if outcome is not None and int(outcome) == ctrl_mod.REJECT:
                return
        self.on_publish(self.acc.params_leafwise(state.params), version)

    def _carve_val_batch(self) -> Optional[PyTree]:
        """Default validation split for vocab models (the synthetic LM
        stream): one batch at the reserved VAL_FOLD stream offset, shaped
        exactly like a training batch. Models without a vocab (e.g. the
        bench MLP adapters) return None — those callers pass
        ``Trainer(val_batch=...)`` or ``fit(eval_batch=...)`` explicitly."""
        mc = getattr(self.model, "cfg", None)
        vocab = getattr(mc, "vocab_size", None) if mc is not None else None
        if not vocab:
            return None
        from repro.data.tokens import validation_batch
        tc = self.acfg.train
        kw = {}
        if getattr(mc, "mrope_sections", None):
            kw["mrope"] = True
        if getattr(mc, "family", "") == "encdec":
            kw["frames"] = (mc.encoder_seq_len, mc.d_model)
        batch = validation_batch(tc.seed, tc.global_batch, tc.seq_len,
                                 vocab, **kw)
        if self.mesh is not None:
            from repro.launch.inputs import gate_batch_shardings
            batch = jax.device_put(batch,
                                   gate_batch_shardings(batch, self.mesh))
        return batch

    # -- state ---------------------------------------------------------------
    def init_state(self, key=None) -> TrainState:
        key = (key if key is not None
               else jax.random.PRNGKey(self.acfg.train.seed))

        def fresh(key):
            params = self.model.init(key)
            bufs = self.acc.init(params) if self.acfg.dmd.enabled else None
            return TrainState(params, self.opt.init(params),
                              jnp.zeros((), jnp.int32), bufs,
                              self.acc.init_grams(bufs),
                              self.acc.init_controller())

        if self.mesh is None:
            return fresh(key)
        # One program builds the state straight into its planned shardings:
        # no leaf (the snapshot arena above all) is ever whole on a device.
        return jax.jit(fresh, out_shardings=self._shardings(
            jax.eval_shape(fresh, key)))(key)

    def _shardings(self, state: TrainState) -> TrainState:
        """NamedShardings of every state leaf (concrete or abstract). DMD
        buffer/Gram specs come from the plan table, so a fresh state starts
        out sharded exactly as the steps keep it, and a checkpoint written
        on one topology restores onto any other."""
        from repro.launch.inputs import shardings_of, state_specs
        return shardings_of(
            state_specs(state, self.mesh,
                        plans=self.acc.plans_for(state.params),
                        arena=self.acc.arena_for(state.params)),
            self.mesh)

    def _place(self, state: TrainState) -> TrainState:
        """Put every state leaf on the mesh with its planned sharding (no-op
        without a mesh)."""
        if self.mesh is None:
            return state
        return jax.tree_util.tree_map(
            lambda x, s: None if x is None else jax.device_put(x, s),
            state, self._shardings(state), is_leaf=lambda x: x is None)

    # -- checkpointing --------------------------------------------------------
    def save(self, state: TrainState, step: int):
        """Checkpoints are always written in the LEAF-WISE layout (arenas
        unpacked into per-leaf buffers/Grams — DESIGN.md §7): on-disk
        format is identical across dmd.arena on/off, so old checkpoints
        load into arena runs and vice versa."""
        if not self.checkpoint_dir:
            return
        from repro.checkpoint import save_checkpoint
        save_checkpoint(self.checkpoint_dir, self.acc.state_leafwise(state),
                        step, keep=self.acfg.train.keep_checkpoints)

    def restore(self, state_like: Optional[TrainState] = None
                ) -> Optional[TrainState]:
        if not self.checkpoint_dir:
            return None
        from repro.checkpoint import restore_checkpoint
        template = state_like if state_like is not None else self.init_state()
        # Leaf-wise on disk (see save): unpack the template's arenas so the
        # manifest paths line up, restore, then re-pack at the end.
        template = self.acc.state_leafwise(template)
        state = restore_checkpoint(self.checkpoint_dir, template,
                                   mesh=self.mesh)
        if state is None:
            return None
        # Elastic restore: re-place every restored leaf against the CURRENT
        # mesh's shardings BEFORE any computation touches the state — the
        # arena-unpacked template can leave buffer leaves committed to the
        # mesh while Gram leaves are single-device (shard_map outputs vs
        # plain slices), which would poison the first jit below with mixed
        # placements.
        state = self._place(state)
        if self.acc.streaming and state.dmd_gram is not None:
            # Pre-streaming checkpoints restore the template's all-zero
            # Grams; rebuild those from the restored buffers so a mid-window
            # resume never applies DMD on a Gram with zeroed rows. Template
            # buffer/Gram shapes come from the same plan table that wrote
            # the checkpoint, so mixed-m (per-group) states round-trip, and
            # every group's window position is re-derived from the restored
            # step index — a mid-window resume with heterogeneous m is
            # bit-exact (tests/test_trainer.py).
            state = state._replace(dmd_gram=snap.recompute_grams(
                state.dmd_gram, state.dmd_buffers, self.acfg.dmd,
                self.acc.plans_for(state.params)))
        return self.acc.state_arenaize(state)

    def _install_preempt_handler(self):
        def handler(signum, frame):
            self._preempted = True
        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass                          # not on the main thread (tests)

    # -- the loop ---------------------------------------------------------------
    def fit(self, batches: Iterator[PyTree], steps: int,
            state: Optional[TrainState] = None,
            log_every: int = 0, on_metrics: Optional[Callable] = None,
            eval_batch: Optional[PyTree] = None) -> TrainState:
        """`eval_batch` (controller mode only) is the held-out microbatch
        the loss gate scores jumps on. None falls back to the trainer's
        persistent validation split (carved at init, disjoint from the
        training stream and step-independent — a preemption-exact resume
        sees the identical gate batch); with ``controller.val_gate=True``
        the validation split is preferred even over an explicit
        `eval_batch`. The gate NEVER draws from the training iterator.
        Sliced to controller.eval_rows rows (clamped to the batch size)."""
        # Host spans (jax.profiler.TraceAnnotation): written into the
        # profiler's trace only while one is taken, on the clock of the
        # device ops; nearly free otherwise.
        with jax.profiler.TraceAnnotation("repro.fit.enter"):
            state, start_step, eval_batch = self._enter(state, eval_batch)
        ckpt_every = self.acfg.train.checkpoint_every
        for step in range(start_step, steps):
            with jax.profiler.StepTraceAnnotation("repro.fit.step",
                                                  step_num=step):
                if self.fail_at_step is not None \
                        and step == self.fail_at_step:
                    raise RuntimeError(f"injected failure at step {step}")
                with jax.profiler.TraceAnnotation("repro.fit.batch"):
                    batch = next(batches)
                with jax.profiler.TraceAnnotation("repro.fit.train_step"):
                    state, metrics = self.train_step(
                        state, batch, jnp.asarray(step, jnp.int32))
                apply_groups = (self.acc.apply_groups(step)
                                if self.acfg.dmd.enabled else ())
                if apply_groups:
                    with jax.profiler.TraceAnnotation("repro.fit.jump",
                                                      groups=apply_groups):
                        state, dmd_info = self._jump(state, step,
                                                     apply_groups,
                                                     eval_batch)
                    metrics.update(dmd_info)
                with jax.profiler.TraceAnnotation("repro.fit.on_metrics"):
                    if log_every and step % log_every == 0:
                        loss = float(metrics["loss"])
                        print(f"step {step}: loss={loss:.6f}")
                    if on_metrics is not None:
                        on_metrics(step, metrics)
                if ckpt_every and (step + 1) % ckpt_every == 0:
                    with jax.profiler.TraceAnnotation(
                            "repro.fit.checkpoint"):
                        self.save(state, step + 1)
                if self._preempted:
                    with jax.profiler.TraceAnnotation(
                            "repro.fit.checkpoint"):
                        self.save(state, step + 1)
                    print(f"preempted: checkpoint saved at step {step + 1}")
                    break
        with jax.profiler.TraceAnnotation("repro.fit.exit"):
            return state_unresident(self.acc, state)

    def _enter(self, state: Optional[TrainState],
               eval_batch: Optional[PyTree]) -> tuple:
        """fit's set-up: (the resident state to train from, its step
        index, the gate's batch)."""
        self._install_preempt_handler()
        resumed = self.restore(state)
        if resumed is not None:
            state = resumed
        elif state is None:
            state = self.init_state()
        # Arena-native residency (DESIGN.md §7, train/step.py): for the
        # duration of the loop the packed leaves' params and elementwise
        # optimizer moments live in the bucket buffers; expanded back
        # before returning, so callers (and checkpoints, via
        # state_leafwise in save) never see the wrapper layout.
        state = state_resident(self.acc, self.acfg, state)
        start_step = int(state.step)
        if not self.controller_on:
            return state, start_step, eval_batch
        ccfg = self.acfg.dmd.controller
        # The ISSUE 9 bugfix: the old fallback `eval_batch =
        # next(batches)` consumed (and scored on) the next TRAINING
        # batch — the gate then measured training-trajectory fit, not
        # generalization, and the stream position shifted by one.
        if getattr(ccfg, "val_gate", False) and self.val_batch is not None:
            eval_batch = self.val_batch
        elif eval_batch is None:
            eval_batch = self.val_batch
        if eval_batch is None:
            raise ValueError(
                "controller mode needs a gate batch disjoint from the "
                "training stream: pass fit(eval_batch=...) or "
                "Trainer(val_batch=...) (vocab models carve one "
                "automatically at init)")
        rows = ccfg.eval_rows
        if rows:
            # clamp to the actual batch size — eval_rows larger than
            # the batch must not silently slice past it
            n_rows = min(int(x.shape[0]) for x in
                         jax.tree_util.tree_leaves(eval_batch))
            rows = min(int(rows), n_rows)
            eval_batch = jax.tree_util.tree_map(
                lambda x: x[:rows], eval_batch)
        return state, start_step, eval_batch

    def _jump(self, state: TrainState, step: int, groups: tuple,
              eval_batch: Optional[PyTree]) -> tuple:
        """Dispatch the jump of the groups whose window closed after
        ``step``, and publish what it did not reject."""
        relax = jnp.asarray(self.acc.relax_vector(step), jnp.float32)
        if self.controller_on:
            state, dmd_info = self.dmd_step(state, relax, eval_batch,
                                            groups=groups)
        else:
            state, dmd_info = self.dmd_step(state, relax, groups=groups)
        if self.on_publish is not None:
            self._publish(state, dmd_info, step + 1)
        return state, dmd_info
