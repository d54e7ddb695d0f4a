"""Launcher plumbing: mesh axis types, the launcher's build() on the
devices it finds, and the compile-cache location rule."""
import jax
from jax.sharding import AxisType

from repro.launch import compile_cache
from repro.launch.mesh import make_mesh, make_mesh_for_devices
from repro.launch.train import build


def test_meshes_are_built_with_auto_axes():
    """jax.make_mesh defaults to Explicit axes, under which the snapshot
    record's dynamic_update_slice is rejected; every repo mesh is Auto."""
    for mesh in (make_mesh((1, 1), ("data", "model")),
                 make_mesh_for_devices(1),
                 make_mesh_for_devices(1, devices=jax.devices()[:1])):
        assert mesh.axis_types == (AxisType.Auto,) * len(mesh.axis_names)


def test_build_uses_the_devices_it_finds():
    """Without --reduced the launcher runs the published config on a
    (data, model) mesh over the devices it is given — not the fixed
    256/512-device production mesh."""
    acfg, model, mesh = build("whisper-base", steps=80, global_batch=2,
                              seq=448, devices=jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert model.cfg.d_model == 512 and model.cfg.n_layers == 6
    assert acfg.train.global_batch == 2 and acfg.train.seq_len == 448
    assert acfg.dmd.warmup_steps == 20            # min(config's 100, 80//4)
    # heads are padded to the model axis (1: 8 heads need no padding)
    assert model.pad_heads_to == 1
    _, _, none_mesh = build("whisper-base", steps=8, reduced=True)
    assert none_mesh is None


def test_compile_cache_follows_env_else_fixed_repo_path(monkeypatch):
    key = "jax_compilation_cache_dir"
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update(key, before)
        compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before   # env rules
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == \
            str(compile_cache.CACHE_DIR)
        assert compile_cache.CACHE_DIR.name == ".jax_cache"
        assert (compile_cache.CACHE_DIR.parent / "chip_smoke.py").exists()
    finally:
        jax.config.update(key, before)
