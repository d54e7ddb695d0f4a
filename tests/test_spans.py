"""The program's profiler spans: the named scopes the compiled train step
and gated jump carry in their HLO op_name metadata, and the host spans
``Trainer.fit`` writes into a profiler trace (one per step, jump, save,
and fit's way in and out)."""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import (DMDConfig, DMDControllerConfig,
                                OptimizerConfig, TrainConfig)
from repro.data.tokens import synthetic_lm_batches
from repro.models.transformer import LanguageModel
from repro.train import Trainer
from repro.train.step import resident_enabled, state_resident

STEPS = 12            # warmup 4, cooldown 2, m 4: one jump, after step 9
CKPT_EVERY = 5


def _trainer(checkpoint_dir=""):
    """A reduced LM with DMD on: arena-resident, streaming Gram, jumps
    gated by the controller."""
    acfg = get_config("tinyllama-1.1b")
    mc = reduced(acfg.model, n_layers=2, d_model=32, d_ff=64, vocab_size=128,
                 n_heads=2, n_kv_heads=1, head_dim=16)
    acfg = dataclasses.replace(
        acfg, model=mc,
        dmd=DMDConfig(enabled=True, m=4, s=10, tol=1e-4, warmup_steps=4,
                      cooldown_steps=2,
                      controller=DMDControllerConfig(enabled=True)),
        optimizer=OptimizerConfig(name="adam", lr=3e-3, schedule="constant"),
        parallel=dataclasses.replace(acfg.parallel, grad_accum=1,
                                     remat="none"),
        train=TrainConfig(global_batch=4, seq_len=16,
                          checkpoint_every=CKPT_EVERY if checkpoint_dir
                          else 0, checkpoint_dir=checkpoint_dir))
    trainer = Trainer(LanguageModel(mc, head_tp=False, chunk_k=16), acfg)
    assert trainer.controller_on and trainer.acc.streaming
    assert resident_enabled(trainer.acc, acfg)
    return trainer, synthetic_lm_batches(0, 4, 16, mc.vocab_size)


# --------------------------------------------------------------------------
# Device scopes: HLO op_name metadata of the compiled programs
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def op_names():
    trainer, batches = _trainer()
    state = state_resident(trainer.acc, trainer.acfg, trainer.init_state())
    step = trainer.train_step.lower(state, next(batches),
                                    jnp.asarray(9, jnp.int32))
    relax = jnp.asarray(trainer.acc.relax_vector(9), jnp.float32)
    jump = trainer.dmd_step.lower(state, relax, trainer.val_batch,
                                  groups=trainer.acc.apply_groups(9))
    return {name: set(re.findall(r'op_name="([^"]*)"',
                                 lowered.compile().as_text()))
            for name, lowered in (("train_step", step), ("dmd_step", jump))}


def _has_scope(names, scope: str) -> bool:
    want = scope.split("/")
    for name in names:
        comps = name.split("/")
        if any(comps[i:i + len(want)] == want
               for i in range(len(comps) - len(want) + 1)):
            return True
    return False


@pytest.mark.parametrize("program, scope", [
    ("train_step", "jvp(forward)"),
    ("train_step", "transpose(jvp(forward))"),
    ("train_step", "jvp(forward)/arena_views"),
    ("train_step", "optimizer"),
    ("train_step", "dmd_record"),
    ("train_step", "gram_row"),
    ("dmd_step", "dmd_jump/solve"),
    ("dmd_step", "dmd_jump/combine"),
    ("dmd_step", "dmd_jump/gate"),
])
def test_compiled_program_carries_scope(op_names, program, scope):
    assert _has_scope(op_names[program], scope), sorted(op_names[program])


def test_gate_forwards_stay_out_of_the_forward_scope(op_names):
    """The gate's loss forwards belong to the jump, not to the train
    step's forward: no op of the jump program sits under ``forward``."""
    assert not any("forward" in n for n in op_names["dmd_step"])
    assert not _has_scope(op_names["train_step"], "dmd_jump")


# --------------------------------------------------------------------------
# Host spans: Trainer.fit under the profiler
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fit_trace(tmp_path_factory):
    from jax.profiler import ProfileData
    trainer, batches = _trainer(str(tmp_path_factory.mktemp("ckpt")))
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        jax.block_until_ready(trainer.fit(batches, steps=STEPS))
    path = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro.fit."):
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns),
                                  dict(ev.stats)))
    jumps = [t for t in range(STEPS) if trainer.acc.apply_groups(t)]
    return sorted(spans, key=lambda s: s[1]), jumps


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _check_enter_exit(spans, jumps):
    enter, exit_ = _named(spans, "repro.fit.enter"), \
        _named(spans, "repro.fit.exit")
    steps = _named(spans, "repro.fit.step")
    assert len(enter) == 1 and len(exit_) == 1
    assert enter[0][2] <= steps[0][1] and steps[-1][2] <= exit_[0][1]


def _check_steps(spans, jumps):
    steps = _named(spans, "repro.fit.step")
    assert [s[3].get("step_num") for s in steps] == list(range(STEPS))


def _check_jumps(spans, jumps):
    found = _named(spans, "repro.fit.jump")
    assert len(jumps) == 1 and len(found) == len(jumps)
    steps = _named(spans, "repro.fit.step")
    assert _inside(found[0], steps[jumps[0]])


def _check_checkpoints(spans, jumps):
    saves = [t for t in range(STEPS) if (t + 1) % CKPT_EVERY == 0]
    found = _named(spans, "repro.fit.checkpoint")
    assert len(found) == len(saves)
    steps = _named(spans, "repro.fit.step")
    assert all(_inside(c, steps[t]) for c, t in zip(found, saves))


def _check_step_parts(spans, jumps):
    for step in _named(spans, "repro.fit.step"):
        for part in ("repro.fit.batch", "repro.fit.train_step",
                     "repro.fit.on_metrics"):
            assert sum(_inside(s, step) for s in _named(spans, part)) == 1


@pytest.mark.parametrize("check", [_check_enter_exit, _check_steps,
                                   _check_jumps, _check_checkpoints,
                                   _check_step_parts],
                         ids=lambda f: f.__name__[len("_check_"):])
def test_fit_host_spans(fit_trace, check):
    check(*fit_trace)
