"""The ``attention_device_ms`` reader on small hand-written traces."""
import pytest

from test_scopes import (B, F, JUMP, MODULES, OPS, TRAIN, view_of,
                         write_xspace)


@pytest.fixture
def read_in(tmp_path, monkeypatch):
    """Reads ``attention_device_ms`` over a trace of the given operations."""
    import bench.harness
    from bench.harness import find_metric
    monkeypatch.setattr(bench.harness, "TRACE_DIR", tmp_path)

    def read(ops, record):
        if ops is not None:
            write_xspace(tmp_path / "run.xplane.pb", MODULES, ops)
        return find_metric("attention_device_ms").read(view_of(), record, {})
    return read


def test_attention_forward_and_backward_per_step(read_in):
    """The attention scope under forward and backward counts, once where
    a kernel's operations nest; the gate's attention (the jump program)
    does not."""
    ops = [
        ("%flash_attention.3 = ...", TRAIN, F + "/while/body/closed_call/"
         "attention/jvp(jit(flash_attention))/pallas_call:", 0, 6),
        ("%fusion.7 = ...", TRAIN, F + "/while/body/closed_call/attention/"
         "pad:", 5, 8),
        ("%fusion.8 = ...", TRAIN, F + "/while/body/dot_general:", 8, 20),
        ("%flash_mha_bwd_dq.1 = ...", TRAIN, B + "/while/body/closed_call/"
         "attention/transpose(jvp(jit(flash_attention)))/pallas_call:",
         20, 30),
        ("%fusion.9 = ...", TRAIN, B + "/while/body/dot_general:", 30, 40),
        ("%flash_attention.4 = ...", JUMP, "jit(dmd_step)/dmd_jump/gate/"
         "closed_call/attention/pallas_call:", 70, 90),
    ]
    # forward 0-8, backward 20-30: 18 ms over two steps
    assert read_in(ops, {"steps": 2}) == pytest.approx(9.0)


@pytest.mark.parametrize("ops", ["no trace", "no attention scope",
                                 "no scope at all"])
def test_none_without_attention_scope(read_in, ops):
    """A run without a trace file, and a program without the attention
    scope (the trace of a parent commit), read nothing."""
    ops = {"no trace": None, "no attention scope": OPS,
           "no scope at all": [(n, p, None, s, e) for n, p, _, s, e in OPS]
           }[ops]
    assert read_in(ops, {"steps": 2, "record_steps": 1}) is None


def test_declared_for_both_whisper_cells(bench_json):
    entry, = [m for m in bench_json["per_layer"]
              if m["name"] == "attention_device_ms"]
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["workloads"] == ["whisper-base.train-dmd",
                                  "whisper-base.train-plain"]
