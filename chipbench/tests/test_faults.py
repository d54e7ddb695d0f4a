"""A run with the timed path broken underneath it comes out not correct.

Each test drives the whole of a run on the CPU at a small size, past the
harness's look for a chip, with one fault planted in the program's train
step: a step that returns its state unchanged, and a step that leaves half
of the batch out and takes the mean over the rest. A sound run at the same
size comes out correct. (A one-chip cell has no exchange between chips to
leave out, and a training cell produces no tokens or answers to alter.)
"""
import json
import time

import pytest

from bench import harness
from conftest import fake_pollutant, tiny_whisper


def unchanged(real):
    def step(state, batch, idx):
        _, metrics = real(state, batch, idx)
        return state, metrics
    return step


def half_batch(real):
    import jax

    def step(state, batch, idx):
        return real(state, jax.tree_util.tree_map(
            lambda x: x[: x.shape[0] // 2], batch), idx)
    return step


FAULTS = {"sound": None, "state_unchanged": unchanged,
          "half_batch": half_batch}


def plant(monkeypatch, fault):
    if fault is None:
        return
    import repro.train.loop as loop
    real_make = loop.make_train_step

    def make(*a, **kw):
        return fault(real_make(*a, **kw))
    monkeypatch.setattr(loop, "make_train_step", make)


def small_pollutant(path):
    def over(config, cell):
        config = json.loads(json.dumps(config))
        cell = json.loads(json.dumps(cell))
        config["data"]["file"] = path
        cell["traffic_params"].update(step_cap=28, pool=2)
        return config, cell
    return over


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["whisper-base.train-dmd",
                                  "pollutant-mlp.time-to-target-nodmd"])
def test_fault_makes_the_run_not_correct(cell, fault, monkeypatch, tmp_path,
                                         bench_json):
    import jax
    plant(monkeypatch, FAULTS[fault])
    over = tiny_whisper if cell.startswith("whisper") else \
        small_pollutant(fake_pollutant(tmp_path))
    line = harness.run(cell, 2 ** 33 + 11, 0.5, False, time.perf_counter(),
                       devices=jax.devices(), config_override=over,
                       bench=bench_json)
    failing = [k for k, v in line["checks"].items()
               if not v["value"] <= v["limit"]]
    if fault == "sound":
        assert line["correct"] is True, line["checks"]
    else:
        assert line["correct"] is False and failing, line["checks"]
