"""A cell, a configuration and a per-layer metric are files found by
name: adding new ones needs no edit to any file already there."""
import json
import time

import pytest

from bench import harness

DRIVER = '''
import time
import jax
import jax.numpy as jnp
from bench.compare import Check
from bench.harness import Outcome, TRACE_DIR
from bench.trace import WINDOW_SPAN


def run(ctx):
    f = jax.jit(lambda x: x * ctx.config["scale"])
    f(jnp.ones(4)).block_until_ready()
    if ctx.trace:
        jax.profiler.start_trace(str(TRACE_DIR))
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        n = 0
        while time.perf_counter() - t0 < ctx.seconds:
            f(jnp.ones(4)).block_until_ready()
            n += 1
    if ctx.trace:
        jax.profiler.stop_trace()
    ok = ctx.module.answer() == 42
    return Outcome({"ops_per_s": n / (time.perf_counter() - t0),
                    "setup_s": t0 - ctx.t_start},
                   {"calls": n}, [Check("answer_gap", 0.0 if ok else 1.0,
                                        0.5)],
                   n, 0, ok, 0)
'''


@pytest.fixture
def tree(tmp_path, monkeypatch):
    for d in ("workloads", "configs", "metrics", "bench"):
        (tmp_path / d).mkdir()
    (tmp_path / "workloads" / "toy.steady.json").write_text(json.dumps(
        {"config": "toy", "traffic": "steady", "driver": "toy_driver",
         "chips": 1, "traffic_params": {}}))
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "scale": 3.0}))
    (tmp_path / "configs" / "toy.py").write_text(
        "def answer():\n    return 42\n")
    (tmp_path / "metrics" / "calls_per_window.py").write_text(
        "def read(view, record, peak):\n"
        "    return record['calls'] / view.window_s\n")
    (tmp_path / "bench" / "toy_driver.py").write_text(DRIVER)
    (tmp_path / "peaks.json").write_text("{}")
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / ".trace")
    return {"end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "ops_per_s", "unit": "ops/s", "workloads": ["toy.steady"]},
        {"name": "other_rate", "unit": "ops/s", "workloads": ["nope"]}],
        "per_layer": [
        {"name": "calls_per_window", "unit": "calls/s", "moves": "ops_per_s"},
        {"name": "not_here", "unit": "%", "moves": "other_rate"}]}


def test_new_cell_config_and_metric_found_by_name(tree):
    import jax
    line = harness.run("toy.steady", 7, 0.2, False, time.perf_counter(),
                       devices=jax.devices(), bench=tree)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "ops_per_s"}
    assert list(line)[-1] == "checks"
    assert line["checks"] == {"answer_gap": {"value": 0.0, "limit": 0.5}}

    line = harness.run("toy.steady", 7, 0.2, True, time.perf_counter(),
                       devices=jax.devices(), bench=tree)
    assert set(line["metrics"]) == {"calls_per_window"}
    assert line["metrics"]["calls_per_window"]["value"] > 0
    assert line["device"]["window_s"] > 0
    assert "breakdown" in line


def test_cell_metrics_follow_benchmark_json(bench_json):
    e2e, per = harness.cell_metrics(bench_json, "whisper-base.train-dmd")
    assert set(e2e) == {"setup_s", "train_tokens_per_s", "peak_hbm_gib"}
    assert "gram_row_roofline" in per and "device_idle_share.ttt" not in per
    for cell in bench_json["workloads"]:
        path = harness.ROOT / "workloads" / f"{cell['name']}.json"
        spec = json.loads(path.read_text())
        assert (spec["config"], spec["traffic"]) == (cell["config"],
                                                     cell["traffic"])
        e2e, per = harness.cell_metrics(bench_json, cell["name"])
        assert "setup_s" in e2e and len(e2e) >= 2 and per
    for m in bench_json["per_layer"]:
        assert hasattr(harness.find_metric(m["name"]), "read")


def test_peak_counts_memory_reserved_for_programs():
    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    assert harness.peak_bytes(Device({"peak_bytes_in_use": 7,
                                      "peak_bytes_reserved": 9})) == 16
    assert harness.peak_bytes(Device({"peak_bytes_in_use": 7})) == 7
    assert harness.peak_bytes(Device(None)) == 0
