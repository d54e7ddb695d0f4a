"""The controls, one precision below what each configuration states, fail
at least one compared number at a size a test run holds (chipbench/
controls.py reads them at the cells' own sizes on the chip)."""
import json

import controls
from bench import harness
from conftest import fake_pollutant, tiny_whisper


def test_whisper_fp8_control_fails():
    cfg, mod = harness.find_config("whisper-base")
    cell = harness.find_cell("whisper-base.train-dmd")
    cfg, cell = tiny_whisper(cfg, cell)
    rows = controls.steady_train(cell, cfg, mod, [5, 6, 7])
    for row in rows:
        over = [k for k, lim in cfg["limits"].items()
                if not row[k] <= lim]
        assert over, row


def test_pollutant_bf16_control_fails(tmp_path):
    cfg, mod = harness.find_config("pollutant-mlp")
    cfg = json.loads(json.dumps(cfg))
    cfg["data"]["file"] = fake_pollutant(tmp_path)
    cell = harness.find_cell("pollutant-mlp.time-to-target-nodmd")
    rows = controls.time_to_target(cell, cfg, mod, [5, 6, 7])
    for row in rows:
        over = [k for k in ("loss_gap", "grad_gap", "update_gap")
                if not row[k] <= cfg["limits"][k]]
        assert over, row
