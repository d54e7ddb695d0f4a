"""Distributed behaviour via subprocess workers (8 virtual host devices).

Single-device equivalence, sharded DMD Gram correctness, int8 cross-pod
gradient sync, and ELASTIC restart (checkpoint written on a (2,2) mesh
restored onto a (4,2) mesh).
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

WORKER = str(Path(__file__).parent / "dist_worker.py")


def run_worker(*args, ndev="8", timeout=600):
    env = dict(os.environ)
    env["TEST_NDEV"] = ndev
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, WORKER, *args],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, f"worker failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout


def _parse(line_prefix, stdout):
    for line in stdout.splitlines():
        if line.startswith(line_prefix):
            return line.split()[1:]
    raise AssertionError(f"{line_prefix} not in output:\n{stdout}")


@pytest.mark.slow
def test_sharded_training_matches_single_device():
    out_sharded = run_worker("train", "2x4")
    out_single = run_worker("train", "1x1", ndev="1")
    l_sh = [float(x) for x in _parse("LOSSES", out_sharded)]
    l_si = [float(x) for x in _parse("LOSSES", out_single)]
    for a, b in zip(l_sh, l_si):
        assert abs(a - b) / max(abs(b), 1e-6) < 2e-2, (l_sh, l_si)


@pytest.mark.slow
def test_multipod_training_runs():
    out = run_worker("train", "2x2x2")
    losses = [float(x) for x in _parse("LOSSES", out)]
    assert losses[-1] < losses[0] * 1.5
    assert all(l == l for l in losses)           # no NaN


def test_sharded_gram_matches_numpy():
    out = run_worker("gram")
    err = float(_parse("GRAM_ERR", out)[0])
    assert err < 1e-5


def test_flash_shard_map_matches_jnp_route():
    """On a (2,2) mesh the flash kernel runs per shard under shard_map
    (batch on data, heads on model) and gives the jnp core's loss and
    gradients."""
    out = run_worker("flash_shard_map", ndev="4")
    assert float(_parse("FLASH_ERR", out)[0]) < 1e-4


def test_int8_cross_pod_gradsync():
    out = run_worker("gradsync")


@pytest.mark.slow
def test_elastic_restart_different_mesh(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    run_worker("elastic_save", ckpt)
    out = run_worker("elastic_restore", ckpt)
    assert "RESTORED" in out


@pytest.mark.slow
@pytest.mark.parametrize("variant,saved_step", [("jump", 6), ("mid", 8)])
def test_controller_preempt_restore_on_remapped_mesh(tmp_path, variant,
                                                     saved_step):
    """ISSUE 4 satellite: SIGTERM lands on the exact jump step ("jump" —
    the checkpoint carries that jump's fresh gate outcome) or mid-window
    ("mid") with the loss-gated controller on, on a (2,2) mesh; restore on
    the REMAPPED (4,2) mesh must resume controller counters, effective s_g,
    relax/gain EMAs, and the cooldown/window phase BIT-EXACTLY (the workers
    print a canonical CTRL line; save and restore must emit it verbatim),
    then finish the run with the remaining gated jumps firing."""
    ckpt = str(tmp_path / f"ckpt_{variant}")
    out_save = run_worker("ctrl_save", ckpt, variant)
    assert f"SAVED {saved_step}" in out_save
    out_restore = run_worker("ctrl_restore", ckpt, str(saved_step))
    assert "CTRL_OK" in out_restore
    line_save = next(l for l in out_save.splitlines()
                     if l.startswith("CTRL "))
    line_restore = next(l for l in out_restore.splitlines()
                        if l.startswith("CTRL "))
    assert line_save == line_restore


@pytest.mark.slow
def test_resident_restore_on_remapped_mesh(tmp_path):
    """ISSUE 7: a run whose params live arena-RESIDENT (adam,
    arena_native on) checkpoints mid-training on a (2,2) mesh — the
    on-disk format is leaf-wise — and restores on the REMAPPED (4,2)
    mesh, where the per-leaf elastic re-placement rebuilds the resident
    sharded buckets for the NEW topology and training continues on the
    resident layout. The params checksum survives the save/remap/restore
    round trip."""
    ckpt = str(tmp_path / "ckpt_resident")
    out_save = run_worker("resident_save", ckpt)
    out_restore = run_worker("resident_restore", ckpt)
    assert "RESIDENT_OK" in out_restore
    saved = float(_parse("SAVED", out_save)[0])
    restored = float(_parse("RESTORED", out_restore)[0])
    assert abs(saved - restored) / max(abs(saved), 1.0) < 1e-5


@pytest.mark.slow
@pytest.mark.parametrize("variant", ["keep", "zero", "hetero"])
def test_gram_restore_on_remapped_mesh(tmp_path, variant):
    """A streaming-era checkpoint (grams carried), a zeroed-gram /
    pre-streaming checkpoint (grams rebuilt by recompute_grams' batched
    staleness pass), and a HETEROGENEOUS two-group checkpoint (norm scales
    on m=3 windows, the rest on m=4) all resume to gram_matrix equality on
    a REMAPPED mesh with per-group buffer/Gram shapes intact."""
    ckpt = str(tmp_path / f"ckpt_{variant}")
    run_worker("gram_save", ckpt, variant)
    out = (run_worker("gram_restore", ckpt, "hetero")
           if variant == "hetero" else run_worker("gram_restore", ckpt))
    assert "GRAMS_OK" in out
