"""The trace reduction on a small hand-written trace."""
import pytest

from bench import trace

MS = 1_000_000


def planes():
    dev = "/device:TPU:0"
    return {
        "/host:CPU": {"python": [
            (trace.WINDOW_SPAN, 0, 100 * MS),
            ("chipbench.fit", 0, 60 * MS),
            ("chipbench.test_mse", 60 * MS, 100 * MS)]},
        dev: {
            "XLA Modules": [("jit_train_step(1)", 10 * MS, 30 * MS),
                            ("jit_train_step(1)", 30 * MS, 50 * MS),
                            ("jit_dmd_step(2)", 70 * MS, 80 * MS),
                            ("jit_train_step(1)", 95 * MS, 120 * MS)],
            "XLA Ops": [("fusion.1", 10 * MS, 25 * MS),
                        ("gram_row_pallas.2", 25 * MS, 30 * MS),
                        ("fusion.1", 30 * MS, 50 * MS),
                        ("combine_pallas.1", 70 * MS, 74 * MS),
                        ("fusion.2", 72 * MS, 80 * MS),      # overlaps
                        ("fusion.1", 95 * MS, 120 * MS)]},    # past the end
    }


def test_busy_idle_and_program_time():
    v = trace.from_events(planes())
    assert v.window_s == pytest.approx(0.1)
    # busy: 10-50, 70-80, 95-100 (clipped) = 55 ms
    assert v.busy_s() == pytest.approx(0.055)
    secs, n = v.module_time(lambda s: "train_step" in s)
    assert n == 3 and secs == pytest.approx(0.045)
    secs, n = v.module_time(lambda s: "dmd_step" in s)
    assert n == 1 and secs == pytest.approx(0.010)
    secs, n = v.op_time(lambda s: "gram_row_pallas.2" in s)
    assert n == 1 and secs == pytest.approx(0.005)
    assert v.top_ops(2)[0] == ["fusion.1", pytest.approx(0.040)]


def test_idle_gaps_named_by_host_span():
    v = trace.from_events(planes())
    gaps = v.idle_gaps(3)
    # 0-10 (fit), 50-70 (gap midpoint 60: test_mse starts there), 80-95
    assert [g[1] for g in gaps] == [pytest.approx(0.020),
                                    pytest.approx(0.015),
                                    pytest.approx(0.010)]
    assert gaps[1][0] == "chipbench.test_mse"
    assert gaps[2][0] == "chipbench.fit"


def test_idle_metrics_read_the_share(bench_json):
    from bench.harness import find_metric
    v = trace.from_events(planes())
    m = find_metric("device_idle_share.train")
    assert m.read(v, {}, {}) == pytest.approx(45.0)
    empty = trace.from_events({"/host:CPU": {"p": [(trace.WINDOW_SPAN, 0,
                                                      MS)]}})
    assert m.read(empty, {}, {}) is None


def test_kernel_roofline_reader_from_counts():
    from bench.harness import find_metric
    v = trace.from_events(planes())
    peak = {"flops_bf16": 1e15, "hbm_bytes_per_s": 1e9}
    rec = {"m": 14, "dmd_lanes": 1000, "snapshot_itemsize": 4,
           "record_steps": 2}
    # gram row: 2 records x 56000 B / 1e9 B/s = 112 us over 5 ms of kernel
    assert find_metric("gram_row_roofline").read(v, rec, peak) == \
        pytest.approx(100 * 2 * 56e-6 / 5e-3)
    rec["record_steps"] = 0
    assert find_metric("gram_row_roofline").read(v, rec, peak) is None


def test_window_span_required():
    with pytest.raises(ValueError):
        trace.from_events({"/host:CPU": {"p": [("x", 0, 1)]}})
