"""Reduce a JAX profiler trace to device busy time, per-program and
per-kernel device time, and the longest idle gaps.

A trace is read once into a ``TraceView``: the device operations and program
(module) executions of every accelerator plane, and the host spans, all
clipped to the measured window. The window is the host span that the harness
writes around it (``WINDOW_SPAN``), so host and device events are read on the
profiler's one clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "chipbench.window"

Event = Tuple[str, int, int]            # (name, start_ns, end_ns)


@dataclass
class TraceView:
    window: Tuple[int, int]                         # (start_ns, end_ns)
    ops: Dict[str, List[Event]] = field(default_factory=dict)     # device
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # device
    host: List[Event] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> List[str]:
        return sorted(set(self.ops) | set(self.modules))

    def busy_intervals(self, device: str) -> List[Tuple[int, int]]:
        """Union of the intervals in which an operation ran on ``device``
        (its programs' executions where the plane has no op line)."""
        evs = self.ops.get(device) or self.modules.get(device) or []
        return merge([(s, e) for _, s, e in evs])

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran anything."""
        devs = self.devices
        if not devs:
            return 0.0
        tot = sum(sum(e - s for s, e in self.busy_intervals(d))
                  for d in devs)
        return tot * 1e-9 / len(devs)

    def module_time(self, match) -> Tuple[float, int]:
        """(seconds, executions) of the programs whose name ``match``
        accepts, summed over devices."""
        return _sum(self.modules, match)

    def op_time(self, match) -> Tuple[float, int]:
        """(seconds, events) of the device operations ``match`` accepts."""
        return _sum(self.ops, match)

    def top_ops(self, k: int = 10) -> List[List]:
        tot: Dict[str, int] = {}
        for evs in self.ops.values():
            for n, s, e in evs:
                tot[n] = tot.get(n, 0) + (e - s)
        n_dev = max(len(self.ops), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9 / n_dev] for n, t in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps of the first device, each named by
        the innermost host span that covers its midpoint."""
        devs = self.devices
        if not devs:
            return []
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy_intervals(devs[0]) + [(hi, hi)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) // 2
            cover = [h for h in self.host
                     if h[1] <= mid <= h[2] and h[0] != WINDOW_SPAN]
            name = (min(cover, key=lambda h: h[2] - h[1])[0] if cover
                    else "host:none")
            out.append([name, (e - s) * 1e-9])
        return out


def merge(iv: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _sum(by_dev: Dict[str, List[Event]], match) -> Tuple[float, int]:
    t, n = 0, 0
    for evs in by_dev.values():
        for name, s, e in evs:
            if match(name):
                t += e - s
                n += 1
    return t * 1e-9, n


def _clip(evs: List[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def from_events(planes: Dict[str, Dict[str, List[Event]]]) -> TraceView:
    """Build the view from ``{plane: {line: [(name, start, end)]}}``: the
    in-memory form of a trace, which tests write by hand."""
    host = [ev for pname, lines in planes.items()
            if pname.startswith("/host:") for evs in lines.values()
            for ev in evs]
    win = [ev for ev in host if ev[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} host span")
    lo, hi = win[0][1], win[0][2]
    view = TraceView((lo, hi), host=_clip(host, lo, hi))
    for pname, lines in planes.items():
        if not is_device_plane(pname):
            continue
        for lname, evs in lines.items():
            evs = _clip(evs, lo, hi)
            if lname == "XLA Ops":
                view.ops[pname] = evs
            elif lname == "XLA Modules":
                view.modules[pname] = evs
    return view


def read_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """The events of an ``.xplane.pb`` file, by plane and line. A device
    operation is named as its HLO instruction (``fusion.12``,
    ``gram_row_pallas.2``): the trace gives the whole instruction text."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    short: Dict[str, str] = {}
    for plane in pd.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                name = ev.name
                if name not in short:
                    short[name] = op_name(name)
                s = int(ev.start_ns)
                evs.append((short[name], s, s + int(ev.duration_ns)))
    return out


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(trace_dir: str) -> TraceView:
    path = find_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_events(read_planes(path))
