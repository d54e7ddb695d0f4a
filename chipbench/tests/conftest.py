"""CPU tests of the benchmark itself: run with
``python -m pytest chipbench/tests`` from the repository root."""
import json
import sys
from pathlib import Path

import pytest

CHIPBENCH = Path(__file__).resolve().parents[1]
for p in (CHIPBENCH, CHIPBENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_WHISPER = dict(d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                    d_ff=128, vocab_size=512, encoder_seq_len=32,
                    max_seq_len=32, n_layers=2, n_encoder_layers=2)


def tiny_whisper(config, cell):
    """whisper-base's cell at a size a CPU test holds."""
    config, cell = json.loads(json.dumps(config)), json.loads(json.dumps(cell))
    config["model"].update(TINY_WHISPER)
    config["reduced"] += sorted(TINY_WHISPER)
    cell["traffic_params"].update(global_batch=2, seq_len=32, ring=4)
    return config, cell


def fake_pollutant(tmp: Path) -> str:
    """A regression set of the paper's shapes (6 inputs, 2670 outputs,
    800 + 200 samples) from a random teacher, to stand in for the ADR data
    file in tests."""
    import numpy as np
    rng = np.random.default_rng(0)
    x = rng.uniform(-1, 1, (1000, 6)).astype(np.float32)
    w1, w2 = rng.normal(size=(6, 64)), rng.normal(size=(64, 2670)) / 8
    y = np.tanh(x @ w1) @ w2
    y = ((y - y.mean()) / y.std()).astype(np.float32)
    path = tmp / "pollutant.npz"
    np.savez(path, x_train=x[:800], y_train=y[:800], x_test=x[800:],
             y_test=y[800:])
    return str(path)


@pytest.fixture
def bench_json():
    with open(CHIPBENCH.parent / "BENCHMARK.json") as f:
        return json.load(f)
