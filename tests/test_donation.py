"""Donation audit: ``donate_argnums=(0,)`` must actually donate the
snapshot buffers and Grams — per-leaf and packed-arena — in the fused
train step and BOTH dmd_step variants.

Since ISSUE 6 the invariant itself lives in the shared static-audit layer
(repro.audit.passes::donation_alias — the same pass the
``python -m repro.audit`` CLI runs): every buffer/Gram leaf appears in
the compiled module's ``input_output_alias`` table, and no copy op of a
buffer/Gram shape survives. This file routes the Trainer's REAL jitted
programs through that pass; no standalone HLO-regex logic remains here.

The plain (ungated) jump reads only the buffers — the param VALUES are
dead, XLA prunes those inputs, and only the pass-through leaves can alias;
the gated (controller) jump reads params for the loss gate, so there the
WHOLE TrainState must alias through (the rollback branch passes the
donated pre-jump params and moments straight through untouched).
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.audit.passes import donation_alias
from repro.audit.targets import adhoc_context, trace_target
from repro.configs import get_config, reduced
from repro.configs.base import (DMDConfig, DMDControllerConfig,
                                OptimizerConfig, TrainConfig)
from repro.data.tokens import synthetic_lm_batches
from repro.models.transformer import LanguageModel
from repro.train import Trainer


def _setup(controller=None, arena=True):
    acfg = get_config("tinyllama-1.1b")
    mc = reduced(acfg.model, n_layers=2, d_model=32, d_ff=64, vocab_size=128,
                 n_heads=2, n_kv_heads=1, head_dim=16)
    acfg = dataclasses.replace(
        acfg, model=mc,
        dmd=DMDConfig(enabled=True, m=4, s=10, tol=1e-4, warmup_steps=4,
                      cooldown_steps=2, arena=arena,
                      controller=controller or DMDControllerConfig()),
        optimizer=OptimizerConfig(name="adam", lr=3e-3, schedule="constant"),
        parallel=dataclasses.replace(acfg.parallel, grad_accum=1,
                                     remat="none"),
        train=TrainConfig(global_batch=4, seq_len=16))
    model = LanguageModel(mc, head_tp=False, chunk_k=16)
    return Trainer(model, acfg), synthetic_lm_batches(0, 4, 16, mc.vocab_size)


def _audit(trainer, name, target):
    """Run the shared donation pass over one Trainer program."""
    ctx = adhoc_context("tinyllama-1.1b-reduced", trainer.acfg,
                        {name: target})
    violations, info = donation_alias(ctx)
    return [v for v in violations if v.severity == "error"], info


@pytest.mark.parametrize("arena", [True, False])
def test_train_step_donates_everything(arena):
    trainer, batches = _setup(arena=arena)
    state = trainer.init_state()
    t = trace_target("train_step", trainer.train_step,
                     (state, next(batches), jnp.asarray(5, jnp.int32)), {},
                     state)
    errors, info = _audit(trainer, "train_step", t)
    assert errors == [], errors
    # the pass pins exact whole-state aliasing for the fused step
    assert info["train_step.alias_count"] == len(
        jax.tree_util.tree_leaves(state))
    assert info["train_step.dmd_copies"] == 0


@pytest.mark.parametrize("arena", [True, False])
def test_plain_dmd_step_donates_buffers_and_grams(arena):
    trainer, _ = _setup(arena=arena)
    state = trainer.init_state()
    relax = jnp.ones((trainer.acc.n_groups,), jnp.float32)
    t = trace_target("dmd_step", trainer.dmd_step, (state, relax),
                     {"groups": (0,)}, state)
    # buffers+grams (and the step scalar) pass through -> must all alias
    errors, info = _audit(trainer, "dmd_step", t)
    assert errors == [], errors
    assert info["dmd_step.alias_count"] >= t.n_dmd_leaves
    assert info["dmd_step.dmd_copies"] == 0


@pytest.mark.parametrize("arena", [True, False])
def test_gated_dmd_step_donates_whole_state(arena):
    """The controller path: accept/scale/reject all thread the donated
    state — every TrainState leaf must alias input to output."""
    trainer, batches = _setup(
        controller=DMDControllerConfig(enabled=True, eval_rows=4),
        arena=arena)
    state = trainer.init_state()
    relax = jnp.ones((trainer.acc.n_groups,), jnp.float32)
    t = trace_target("dmd_step_gated", trainer.dmd_step,
                     (state, relax, next(batches)), {"groups": (0,)}, state)
    errors, info = _audit(trainer, "dmd_step_gated", t)
    assert errors == [], errors
    assert info["dmd_step_gated.alias_count"] == len(
        jax.tree_util.tree_leaves(state))
    assert info["dmd_step_gated.dmd_copies"] == 0


def test_dropped_donation_is_caught():
    """Mutation check riding the same build: compiling WITHOUT
    donate_argnums must flip the pass to failing (the audit lane bites —
    ISSUE 6 acceptance)."""
    from repro.train.step import audit_step_fns

    trainer, batches = _setup()
    state = trainer.init_state()
    _, fns = audit_step_fns(trainer.model, trainer.acfg, acc=trainer.acc,
                            donate=False)
    t = trace_target("train_step", fns["train_step"],
                     (state, next(batches), jnp.asarray(5, jnp.int32)), {},
                     state, donated=False)
    errors, _ = _audit(trainer, "train_step", t)
    assert errors, "donation pass failed to flag an undonated train step"


def test_copy_scan_counts_buffer_copies_only():
    """The copy scan behind the donation pass: a donated buffer that must
    also be kept alive is copied at the top level and is flagged; a
    layout change inside a fusion body (how the CPU backend feeds the
    column-major LAPACK ``eigh`` from a row-major Gram it only reads)
    materializes no buffer and is not."""
    from repro.audit import hlo as H

    g = jnp.zeros((16, 4, 4))
    kept = jax.jit(lambda g, b: (g.at[0].set(b), g),
                   donate_argnums=(0,)).lower(g, jnp.ones((4, 4)))
    assert H.copy_ops(kept.compile().as_text(), {"f32[16,4,4]"})

    def solve(g1, g2):                  # Grams pass through, as in dmd_step
        gcat = jnp.concatenate([g1, g2])
        w, v = jnp.linalg.eigh(gcat)
        return g1, g2, w, v, jnp.diagonal(gcat, axis1=-2, axis2=-1)

    hlo = jax.jit(solve, donate_argnums=(0, 1)).lower(
        g, jnp.zeros((5, 4, 4))).compile().as_text()
    assert H.copy_ops(hlo, {"f32[16,4,4]", "f32[5,4,4]"}) == []


_FUSED_COPY_HLO = """\
%fused_computation (param_0: f32[16,4,4], param_1: f32[4,4]) -> {out} {{
  %param_0 = f32[16,4,4]{{2,1,0}} parameter(0)
  %copy.1 = f32[16,4,4]{{2,1,0}} copy(%param_0)
  %param_1 = f32[4,4]{{1,0}} parameter(1)
  {body}
}}

ENTRY %main (g: f32[16,4,4], b: f32[4,4]) -> {out} {{
  %g = f32[16,4,4]{{2,1,0}} parameter(0)
  %b = f32[4,4]{{1,0}} parameter(1)
  ROOT %fusion = {out} fusion(%g, %b), kind=kLoop, calls=%fused_computation
}}
"""


@pytest.mark.parametrize("out,body", [
    # multi-output fusion: the copy is an element of the ROOT tuple
    ("(f32[16,4,4]{2,1,0}, f32[4,4]{1,0})",
     "%neg = f32[4,4]{1,0} negate(%param_1)\n"
     "  ROOT %tuple = (f32[16,4,4]{2,1,0}, f32[4,4]{1,0}) "
     "tuple(%copy.1, %neg)"),
    # dynamic-update-slice into the copied buffer: a new buffer of the
    # donated shape leaves the fusion
    ("f32[16,4,4]{2,1,0}",
     "%c = s32[] constant(0)\n"
     "  %r = f32[1,4,4]{2,1,0} reshape(%param_1)\n"
     "  ROOT %dus = f32[16,4,4]{2,1,0} dynamic-update-slice(%copy.1, %r, "
     "%c, %c, %c)"),
], ids=["multi_output_tuple", "dynamic_update_slice"])
def test_copy_scan_flags_fused_copies_that_leave_the_fusion(out, body):
    """A copy inside a fusion body still counts when its buffer can leave
    the fusion: as an element of a multi-output fusion's ROOT tuple, or
    through a fusion whose output has the donated shape."""
    from repro.audit import hlo as H

    hlo = _FUSED_COPY_HLO.format(out=out, body=body)
    assert H.copy_ops(hlo, {"f32[16,4,4]"}) == ["f32[16,4,4]"]
