"""The scope and span readers on small hand-written traces."""
import pytest

from bench import scopes, trace

MS = 1_000_000
TRAIN, JUMP = 11, 22                   # program ids
TF_OP, PROGRAM = 1, 2                  # stat metadata ids


def write_xspace(path, modules, ops):
    """An ``.xplane.pb`` with one TPU plane: ``modules`` as
    (name, program id, start ms, end ms), ``ops`` as (HLO name, program
    id, tf_op or None, start ms, end ms)."""
    space = scopes.messages()["XSpace"]()
    plane = space.planes.add(name="/device:TPU:0")
    for sid, name in ((TF_OP, "tf_op"), (PROGRAM, "program_id")):
        plane.stat_metadata[sid].name = name
    mid = 0
    for line_name, rows in (("XLA Modules", modules), ("XLA Ops", ops)):
        line = plane.lines.add(name=line_name, timestamp_ns=0)
        for row in rows:
            mid += 1
            md = plane.event_metadata[mid]
            if line_name == "XLA Modules":
                name, pid, s, e = row
                md.name = f"{name}({pid})"
            else:
                md.name, pid, tf_op, s, e = row
                md.stats.add(metadata_id=PROGRAM, uint64_value=pid)
                if tf_op is not None:
                    md.stats.add(metadata_id=TF_OP, str_value=tf_op)
            line.events.add(metadata_id=mid, offset_ps=s * MS * 1000,
                            duration_ps=(e - s) * MS * 1000)
    path.write_bytes(space.SerializeToString())


def view_of(window=(0, 100)):
    lo, hi = window
    return trace.from_events({"/host:CPU": {"python": [
        (trace.WINDOW_SPAN, lo * MS, hi * MS)]}})


F = "jit(train_step)/jvp(forward)"
B = "jit(train_step)/transpose(jvp(forward))"
MODULES = [("jit_train_step", TRAIN, 0, 60), ("jit_dmd_step", JUMP, 70, 90)]
OPS = [
    # forward: a loop the compiler left without op_name, its body nested
    ("%while.5 = ...", TRAIN, None, 0, 20),
    ("%fusion.1 = ...", TRAIN, F + "/while/body/dot_general:", 1, 9),
    ("%fusion.2 = ...", TRAIN, F + "/while/body/add:", 10, 19),
    # backward
    ("%fusion.3 = ...", TRAIN, B + "/while/body/dot_general:", 20, 40),
    ("%fusion.4 = ...", TRAIN, "jit(train_step)/optimizer/sub:", 40, 45),
    ("%cond.9 = ...", TRAIN, None, 45, 55),
    ("%gram_row_pallas.2 = ...", TRAIN,
     "jit(train_step)/dmd_record/cond/branch_1_fun/gram_row/x:", 46, 54),
    ("%copy.1 = ...", TRAIN, None, 55, 58),             # unscoped
    # the jump: the same short name as a train-step op, another scope
    ("%fusion.1 = ...", JUMP, "jit(dmd_step)/dmd_jump/gate/dot_general:",
     70, 90),
]


@pytest.fixture
def traced(tmp_path, monkeypatch):
    import bench.harness
    write_xspace(tmp_path / "run.xplane.pb", MODULES, OPS)
    monkeypatch.setattr(bench.harness, "TRACE_DIR", tmp_path)
    return tmp_path


def test_scope_path_without_type():
    assert scopes.scope_of(F + "/while:") == ("jit(train_step)",
                                             "jvp(forward)", "while")
    assert scopes.scope_of("") == ()


def test_nested_events_counted_once():
    ev = {"d": [scopes.Op("jit_train_step", ("jvp(forward)",), 0, 10),
                scopes.Op("jit_train_step", ("jvp(forward)",), 2, 5),
                scopes.Op("jit_train_step", ("jvp(forward)",), 8, 12)],
          "e": [scopes.Op("jit_train_step", ("jvp(forward)",), 0, 4)]}
    secs, n = scopes.scoped_seconds(ev, scopes.forward)
    assert n == 4 and secs == pytest.approx((12 + 4) / 2 * 1e-9)


def test_loop_without_op_name_takes_its_body_scope(traced):
    ops = scopes.read_ops(str(traced / "run.xplane.pb"))["/device:TPU:0"]
    by = {(op.start // MS, op.end // MS): op for op in ops}
    assert by[(0, 20)].scope == ("jit(train_step)", "jvp(forward)", "while",
                                 "body")
    assert by[(45, 55)].scope[:3] == ("jit(train_step)", "dmd_record",
                                      "cond")
    assert by[(55, 58)].scope == ()


def test_programs_sharing_a_short_name_keep_their_scopes(traced):
    ops = scopes.read_ops(str(traced / "run.xplane.pb"))["/device:TPU:0"]
    fusion1 = [op for op in ops if op.start in (1 * MS, 70 * MS)]
    assert {(op.program, op.scope[1]) for op in fusion1} == {
        ("jit_train_step", "jvp(forward)"), ("jit_dmd_step", "dmd_jump")}


def test_forward_backward_and_the_rest_per_step(traced, bench_json):
    from bench.harness import find_metric
    v, rec = view_of(), {"steps": 2, "record_steps": 1}
    read = {m: find_metric(m).read(v, rec, {}) for m in (
        "forward_device_ms", "backward_device_ms", "optimizer_device_ms",
        "dmd_record_device_ms", "unscoped_step_device_ms")}
    # the gate's forward (dmd_step, 70-90) counts nowhere
    assert read == {"forward_device_ms": pytest.approx(10.0),
                    "backward_device_ms": pytest.approx(10.0),
                    "optimizer_device_ms": pytest.approx(2.5),
                    "dmd_record_device_ms": pytest.approx(10.0),
                    "unscoped_step_device_ms": pytest.approx(1.5)}


def test_window_clips_operations(traced):
    from bench.harness import find_metric
    v = view_of(window=(5, 30))
    # forward 5-20, backward 20-30
    assert find_metric("forward_device_ms").read(v, {"steps": 1}, {}) == \
        pytest.approx(15.0)
    assert find_metric("backward_device_ms").read(v, {"steps": 1}, {}) == \
        pytest.approx(10.0)
    assert find_metric("optimizer_device_ms").read(v, {"steps": 1}, {}) \
        is None


@pytest.mark.parametrize("metric", [
    "forward_device_ms", "backward_device_ms", "optimizer_device_ms",
    "dmd_record_device_ms", "unscoped_step_device_ms"])
def test_none_without_scopes(tmp_path, monkeypatch, metric):
    """A program that names no scope (the trace of a parent commit) and a
    run without a trace file both read nothing."""
    import bench.harness
    from bench.harness import find_metric
    monkeypatch.setattr(bench.harness, "TRACE_DIR", tmp_path)
    rec = {"steps": 2, "record_steps": 1}
    assert find_metric(metric).read(view_of(), rec, {}) is None
    write_xspace(tmp_path / "bare.xplane.pb", MODULES,
                 [(n, p, None, s, e) for n, p, _, s, e in OPS])
    assert find_metric(metric).read(view_of(), rec, {}) is None


def host_view():
    """Two fit calls of two steps each; the device runs 12-14 and 16-18
    in the first call's steps, and 50-70 (the driver's own work)."""
    host = [("chipbench.fit", 10, 30), ("repro.fit.enter", 10, 11),
            ("repro.fit.step", 11, 15), ("repro.fit.step", 15, 19),
            ("repro.fit.exit", 19, 20),
            ("repro.fit.enter", 30, 32), ("repro.fit.step", 32, 36),
            ("repro.fit.step", 36, 40), ("repro.fit.exit", 40, 42)]
    host = [(n, s * MS, e * MS) for n, s, e in host]
    planes = {"/host:CPU": {"python": [(trace.WINDOW_SPAN, 0, 100 * MS)]
                            + host},
              "/device:TPU:0": {"XLA Ops": [("fusion.1", 12 * MS, 14 * MS),
                                            ("fusion.1", 16 * MS, 18 * MS),
                                            ("fusion.2", 50 * MS, 70 * MS)]}}
    return trace.from_events(planes)


def test_host_span_metrics(bench_json):
    from bench.harness import find_metric
    v = host_view()
    assert find_metric("host_step_ms.ttt").read(v, {}, {}) == \
        pytest.approx(4.0)
    # (1 + 1) + (2 + 2) ms over two fit calls
    assert find_metric("fit_overhead_ms.ttt").read(v, {}, {}) == \
        pytest.approx(3.0)
    # fit spans cover 10-20 and 30-42 (22 ms), the device 4 ms of them
    idle_fit = find_metric("device_idle_in_fit.ttt").read(v, {}, {})
    assert idle_fit == pytest.approx(18.0)
    assert idle_fit <= find_metric("device_idle_share.ttt").read(v, {}, {})


def test_host_span_metrics_none_without_spans():
    from bench.harness import find_metric
    v = trace.from_events({"/host:CPU": {"p": [(trace.WINDOW_SPAN, 0, MS)]},
                           "/device:TPU:0": {"XLA Ops": [("f", 0, 10)]}})
    for m in ("host_step_ms.ttt", "fit_overhead_ms.ttt",
              "device_idle_in_fit.ttt"):
        assert find_metric(m).read(v, {}, {}) is None


def test_new_metrics_are_declared(bench_json):
    names = {m["name"] for m in bench_json["per_layer"]}
    assert {"forward_device_ms", "backward_device_ms", "optimizer_device_ms",
            "unscoped_step_device_ms", "dmd_record_device_ms",
            "host_step_ms.ttt", "fit_overhead_ms.ttt",
            "device_idle_in_fit.ttt"} <= names
