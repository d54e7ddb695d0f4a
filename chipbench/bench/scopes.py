"""Device time by the program's own scopes, and host time by its spans.

The program names its device work with ``jax.named_scope`` (forward,
optimizer, dmd_record, ...). XLA keeps the scope path in each HLO
instruction's ``op_name`` metadata, and a TPU trace carries it on every
device operation as the ``tf_op`` stat of the operation's event metadata,
beside the ``program_id`` of the program it belongs to::

    jit(train_step)/transpose(jvp(forward))/while:

``jax.profiler.ProfileData`` gives events without their metadata's stats,
so the device operations are read here from the ``.xplane.pb`` file with
protobuf, through a descriptor of the few fields this needs (the XSpace
schema of tsl/profiler/protobuf/xplane.proto, same field numbers). The
short operation name (``fusion.12``) repeats across programs and says
nothing of scope: it is not used.

Device operations nest (a ``while`` contains its body, a ``cond`` its
branch), so the time of a set of operations is the union of their
intervals on each device, averaged over devices.

The program's host spans (``repro.fit.*``, ``jax.profiler.TraceAnnotation``
in ``Trainer.fit``) are read from ``TraceView.host``, which already holds
every host span of the window.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, \
    Tuple

from bench import trace

SCOPE_STAT = "tf_op"
PROGRAM_STAT = "program_id"
FIT_SPAN = "repro.fit."


class Op(NamedTuple):
    """One device operation: its program's name (``jit_train_step``), its
    scope path split at ``/``, and its interval on the profiler's clock."""
    program: str
    scope: Tuple[str, ...]
    start: int
    end: int


Events = Dict[str, List[Op]]                    # device plane -> operations


# --------------------------------------------------------------------------
# The .xplane.pb file
# --------------------------------------------------------------------------

_FIELDS = {   # message -> [(field, number, type, label, message type)]
    "XSpace": [("planes", 1, "message", "repeated", "XPlane")],
    "XPlane": [("name", 2, "string", "optional", None),
               ("lines", 3, "message", "repeated", "XLine"),
               ("event_metadata", 4, "message", "repeated",
                "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, "message", "repeated",
                "XPlane.StatMetadataEntry")],
    "XPlane.EventMetadataEntry": [
        ("key", 1, "int64", "optional", None),
        ("value", 2, "message", "optional", "XEventMetadata")],
    "XPlane.StatMetadataEntry": [
        ("key", 1, "int64", "optional", None),
        ("value", 2, "message", "optional", "XStatMetadata")],
    "XLine": [("name", 2, "string", "optional", None),
              ("timestamp_ns", 3, "int64", "optional", None),
              ("events", 4, "message", "repeated", "XEvent")],
    "XEvent": [("metadata_id", 1, "int64", "optional", None),
               ("offset_ps", 2, "int64", "optional", None),
               ("duration_ps", 3, "int64", "optional", None)],
    "XStat": [("metadata_id", 1, "int64", "optional", None),
              ("uint64_value", 3, "uint64", "optional", None),
              ("int64_value", 4, "int64", "optional", None),
              ("str_value", 5, "string", "optional", None),
              ("ref_value", 7, "uint64", "optional", None)],
    "XEventMetadata": [("name", 2, "string", "optional", None),
                       ("stats", 5, "message", "repeated", "XStat")],
    "XStatMetadata": [("name", 2, "string", "optional", None)],
}
_PACKAGE = "chipbench.xplane"


@functools.lru_cache(maxsize=None)
def messages() -> Dict[str, type]:
    """The message classes, by name, built once in a private pool."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package=_PACKAGE, syntax="proto3")
    protos = {}
    for full in sorted(_FIELDS, key=lambda n: n.count(".")):
        parent, _, name = full.rpartition(".")
        msg = (protos[parent].nested_type.add() if parent
               else fdp.message_type.add())
        msg.name = name
        if name.endswith("Entry"):
            msg.options.map_entry = True
        for fname, num, ftype, label, mtype in _FIELDS[full]:
            f = msg.field.add(name=fname, number=num,
                              type=getattr(F, "TYPE_" + ftype.upper()),
                              label=getattr(F, "LABEL_" + label.upper()))
            if mtype:
                f.type_name = f".{_PACKAGE}.{mtype}"
        protos[full] = msg
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.{name}"))
        for name in _FIELDS}


def _stat_str(stat, stat_names: Dict[int, str]) -> str:
    """A string stat's value, held inline or as a reference to the plane's
    stat-metadata names."""
    return stat.str_value or stat_names.get(stat.ref_value, "")


def scope_of(tf_op: str) -> Tuple[str, ...]:
    """``jit(f)/jvp(forward)/while:`` -> ('jit(f)', 'jvp(forward)',
    'while'): the op_name path without its trailing ``:type``."""
    head, sep, _ = tf_op.rpartition(":")
    return tuple(c for c in (head if sep else tf_op).split("/") if c)


def _program_of_module(name: str) -> Tuple[str, Optional[int]]:
    """``jit_train_step(1360...)`` -> ('jit_train_step', 1360...)."""
    head, _, tail = name.rpartition("(")
    try:
        return head, int(tail.rstrip(")"))
    except ValueError:
        return name, None


def read_ops(path: str) -> Events:
    """Every device operation of the trace file at ``path``, by device."""
    space = messages()["XSpace"]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Events = {}
    for plane in space.planes:
        if not trace.is_device_plane(plane.name):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        lines = {ln.name: ln for ln in plane.lines}
        programs: Dict[int, str] = {}
        for ev in getattr(lines.get("XLA Modules"), "events", ()):
            name, pid = _program_of_module(
                plane.event_metadata[ev.metadata_id].name)
            if pid is not None:
                programs[pid] = name
        meta: Dict[int, Tuple[Optional[int], Tuple[str, ...]]] = {}
        for mid, md in plane.event_metadata.items():
            pid, scope = None, ()
            for st in md.stats:
                key = stat_names.get(st.metadata_id)
                if key == SCOPE_STAT:
                    scope = scope_of(_stat_str(st, stat_names))
                elif key == PROGRAM_STAT:
                    pid = st.uint64_value or st.int64_value
            meta[mid] = (pid, scope)
        ln = lines.get("XLA Ops")
        if ln is None:
            continue
        ops = []
        t0 = ln.timestamp_ns
        for ev in ln.events:
            pid, scope = meta.get(ev.metadata_id, (None, ()))
            s = t0 + ev.offset_ps // 1000
            ops.append(Op(programs.get(pid, ""), scope, s,
                          s + ev.duration_ps // 1000))
        out[plane.name] = inherit_scopes(ops)
    return out


def inherit_scopes(ops: Sequence[Op]) -> List[Op]:
    """Give each operation that carries no scope and contains others (the
    compiler leaves a ``while`` or ``conditional`` without ``op_name``) the
    longest scope path its contained operations share, innermost first, so
    a loop's own time between its body's operations goes to the body's
    scope."""
    ops = sorted(ops, key=lambda op: (op.start, -op.end))
    parent = [-1] * len(ops)
    stack: List[int] = []
    for i, op in enumerate(ops):
        while stack and ops[stack[-1]].end <= op.start:
            stack.pop()
        if stack and op.end <= ops[stack[-1]].end:
            parent[i] = stack[-1]
        stack.append(i)
    shared: List[Optional[Tuple[str, ...]]] = [None] * len(ops)
    out = list(ops)
    for i in range(len(ops) - 1, -1, -1):
        if not out[i].scope and shared[i]:
            out[i] = out[i]._replace(scope=shared[i])
        j, scope = parent[i], out[i].scope
        if j >= 0 and scope:
            shared[j] = scope if shared[j] is None \
                else _common(shared[j], scope)
    return out


def _common(a: Tuple[str, ...], b: Tuple[str, ...]) -> Tuple[str, ...]:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return a[:n]


@functools.lru_cache(maxsize=1)
def _window_ops(path: str, window: Tuple[int, int]) -> Events:
    lo, hi = window
    return {dev: [op._replace(start=max(op.start, lo), end=min(op.end, hi))
                  for op in ops if op.end > lo and op.start < hi]
            for dev, ops in read_ops(path).items()}


def device_ops(view) -> Optional[Events]:
    """The device operations of the traced window the harness reduced into
    ``view`` (its ``.xplane.pb`` file, read once); None without a trace."""
    from bench.harness import TRACE_DIR
    path = trace.find_xplane(str(TRACE_DIR))
    if path is None:
        return None
    return _window_ops(path, tuple(view.window))


# --------------------------------------------------------------------------
# Reductions
# --------------------------------------------------------------------------

def measure(iv: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in trace.merge(iv))


def overlap(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]
            ) -> int:
    """Nanoseconds covered by both interval sets."""
    a, b = trace.merge(a), trace.merge(b)
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(hi - lo, 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def scoped_seconds(events: Events, match: Callable[[Op], bool]
                   ) -> Tuple[float, int]:
    """(seconds, operations): the union of the intervals of the operations
    ``match`` accepts, on each device, averaged over the devices."""
    if not events:
        return 0.0, 0
    tot, n = 0, 0
    for ops in events.values():
        iv = [(op.start, op.end) for op in ops if match(op)]
        n += len(iv)
        tot += measure(iv)
    return tot * 1e-9 / len(events), n


def in_train_step(op: Op) -> bool:
    """The train step program, as ``train_step_device_ms`` decides it."""
    return "train_step" in op.program


def forward(op: Op) -> bool:
    return in_train_step(op) and "jvp(forward)" in op.scope \
        and not any(c.startswith("transpose(") for c in op.scope)


def backward(op: Op) -> bool:
    return in_train_step(op) and "transpose(jvp(forward))" in op.scope


def optimizer(op: Op) -> bool:
    return in_train_step(op) and "optimizer" in op.scope


def dmd_record(op: Op) -> bool:
    return in_train_step(op) and "dmd_record" in op.scope


STEP_SCOPES = (forward, backward, optimizer, dmd_record)


def scoped(op: Op) -> bool:
    return any(m(op) for m in STEP_SCOPES)


def per_step_ms(view, record: dict, match: Callable[[Op], bool],
                per: str = "steps") -> Optional[float]:
    """Milliseconds of the operations ``match`` accepts per ``record[per]``;
    None where no operation matches or nothing was counted."""
    events = device_ops(view)
    if not events or not record.get(per):
        return None
    secs, n = scoped_seconds(events, match)
    return 1e3 * secs / record[per] if n else None


def unscoped_seconds(events: Events) -> Optional[float]:
    """Seconds in which a train-step operation ran but none under the four
    step scopes did, averaged over devices; None where no operation is
    under any of them (a program without scopes)."""
    in_s, n_in = scoped_seconds(events, scoped)
    if not n_in:
        return None
    return scoped_seconds(events, in_train_step)[0] - in_s


# --------------------------------------------------------------------------
# Host spans
# --------------------------------------------------------------------------

def spans(view, name: str) -> List[Tuple[int, int]]:
    """Intervals of the host spans called ``name`` in the window."""
    return [(s, e) for n, s, e in view.host if n == name]


def idle_within(view, iv: Sequence[Tuple[int, int]]) -> float:
    """Seconds inside the intervals ``iv`` in which no operation ran on the
    device, averaged over devices."""
    devs = view.devices
    if not devs:
        return 0.0
    inside = measure(iv)
    tot = sum(inside - overlap(iv, view.busy_intervals(d)) for d in devs)
    return tot * 1e-9 / len(devs)


def fit_spans(view) -> List[Tuple[int, int]]:
    return [(s, e) for n, s, e in view.host if n.startswith(FIT_SPAN)]
