"""One run of one cell: find its files by name, check the device, hand the
cell to its driver, reduce the trace, and build the result line.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric lives in a file of its own, found by name:

- ``workloads/<cell>.json``: the cell's configuration name, its traffic
  mix's name, the driver that runs that kind of traffic, and the traffic's
  parameters;
- ``configs/<config>.json``: the configuration as it is run, and beside it
  ``configs/<config>.py``, its program builder and plain reference;
- ``metrics/<metric>.py``: one per-layer metric's reader;
- ``bench/<driver>.py``: a general driver for one kind of traffic.

Which metrics a cell reports comes from ``BENCHMARK.json`` at the checkout's
root.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]          # chipbench/
CHECKOUT = ROOT.parent
TRACE_DIR = ROOT / ".trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Ctx:
    """What a driver gets: the cell, its configuration and run options."""
    name: str
    cell: dict
    config: dict
    module: object
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    compile_log: object
    peak: dict


@dataclass
class Outcome:
    """What a driver returns."""
    e2e: Dict[str, float]
    record: Dict[str, float]
    checks: list
    attempted: int
    failed: int
    correct: bool
    memory_peak_bytes: int


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(name: str) -> dict:
    path = ROOT / "workloads" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no cell file {path}")
    return load_json(path)


def find_config(name: str):
    cfg = load_json(ROOT / "configs" / f"{name}.json")
    return cfg, load_module(ROOT / "configs" / f"{name}.py",
                            f"chipbench_config_{name.replace('-', '_')}")


def find_metric(name: str):
    return load_module(ROOT / "metrics" / f"{name}.py",
                       f"chipbench_metric_{name.replace('.', '_')}")


def cell_metrics(bench: dict, cell: str) -> tuple:
    """(end-to-end names, per-layer names) that ``cell`` reports: a metric
    with a ``workloads`` list is reported in those cells; a per-layer
    metric without one wherever its ``moves`` metric is reported."""
    e2e = [m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    per = [m["name"] for m in bench["per_layer"]
           if cell in m.get("workloads", [cell] if m["moves"] in e2e
                            else [])]
    return e2e, per


def units(bench: dict) -> Dict[str, str]:
    return {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}


def device_check(chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def peak_bytes(device) -> int:
    """The device's peak memory: the allocator's peak of buffers in use
    plus its peak reserve for compiled programs' temporaries, which XLA:TPU
    keeps apart from the buffers (``peak_bytes_reserved``)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) \
        + int(stats.get("peak_bytes_reserved", 0))


def import_program() -> None:
    """Put the program's sources on the path; fail where they are absent."""
    src = CHECKOUT / "src"
    if not (src / "repro" / "train" / "loop.py").is_file():
        raise FileNotFoundError(f"the program is not in this checkout "
                                f"({src} has no repro/train/loop.py)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def enable_cache() -> None:
    """The program's persistent compilation cache, at its fixed place in
    the checkout (or where JAX_COMPILATION_CACHE_DIR says), for programs of
    every compile time, so a later run compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        *, devices: Optional[list] = None, config_override=None,
        bench: Optional[dict] = None) -> dict:
    """Run cell ``name`` once and return the result line. ``devices`` is
    given only by tests, which drive a run without the chip;
    ``config_override(config, cell)`` lets them shrink the sizes."""
    cell = find_cell(name)
    bench = bench if bench is not None else load_json(
        CHECKOUT / "BENCHMARK.json")
    if devices is None:
        devices = device_check(int(cell.get("chips", 1)))
    import_program()
    if devices[0].platform == "tpu":
        enable_cache()
    from bench.compile_log import CompileLog

    config, module = find_config(cell["config"])
    if config_override is not None:
        config, cell = config_override(config, cell)
    peaks = load_json(ROOT / "peaks.json")
    kind = devices[0].device_kind
    peak = peaks.get(kind)
    if peak is None and devices[0].platform == "tpu":
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    driver = load_module(ROOT / "bench" / f"{cell['driver']}.py",
                         f"chipbench_driver_{cell['driver']}")
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    ctx = Ctx(name, cell, config, module, seed, seconds, trace, t_start,
              devices, CompileLog(), peak or {})
    out: Outcome = driver.run(ctx)

    e2e_names, per_names = cell_metrics(bench, name)
    unit = units(bench)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(out.memory_peak_bytes)}
    line = {"correct": bool(out.correct), "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": {}, "device": device}
    if trace:
        from bench import trace as trace_mod
        view = trace_mod.load(str(TRACE_DIR))
        device["busy_s"] = view.busy_s()
        device["window_s"] = view.window_s
        for m in per_names:
            v = find_metric(m).read(view, out.record, ctx.peak)
            if v is not None:
                line["metrics"][m] = {"value": v, "unit": unit[m]}
        line["breakdown"] = {"device_ops": view.top_ops(10),
                             "idle_gaps": view.idle_gaps(10)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        for m in e2e_names:
            if m in out.e2e:
                line["metrics"][m] = {"value": out.e2e[m], "unit": unit[m]}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def checks_text(line: dict) -> List[str]:
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in line.get("checks", {}).items()]
