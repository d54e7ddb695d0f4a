"""Main-path Pallas kernels compiled for a described TPU v5e, not run.

Interpret mode (every other kernel test) cannot see the TPU compiler's
refusals: blocks off the (8, 128) tiling, too much VMEM, or a buffer laid
out so that every call copies it whole first. These tests hand the real
compiler the shapes of whisper-base's DMD state — the arena bucket of its
real block count at m=14 — and check what it says, including each
program's temporary memory against the snapshot buffer's own size.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and each pytest-xdist worker imports
every test file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.kernels import arena as ka
from repro.kernels.combine import combine_pallas
from repro.kernels.gram_row import gram_row_pallas

M = 14                       # whisper-base's DMD window (configs/whisper_base)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def whisper_bucket():
    """(n_blocks, n_sys, block_n) of whisper-base's largest arena bucket,
    from the same plan/arena tables the Trainer uses (abstract params —
    nothing is allocated)."""
    from repro.configs import get_config
    from repro.core.accelerator import DMDAccelerator
    from repro.models.transformer import LanguageModel

    acfg = get_config("whisper-base")
    model = LanguageModel(acfg.model, head_tp=True)
    params = model.init(abstract=True)
    acc = DMDAccelerator(acfg.dmd, stack_dims=model.param_stack_dims())
    b = max(acc.arena_for(params).values(), key=lambda b: b.n_blocks)
    assert b.m == M
    return b.n_blocks, b.n_sys, b.block_n


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _block_sys(nb, n_sys):
    return np.sort(np.arange(nb) % n_sys).astype(np.int32)


@pytest.mark.parametrize("kernel", ["gram_row", "combine"])
def test_perleaf_kernel_compiles(one_chip, kernel):
    n = 1 << 20
    x = jax.ShapeDtypeStruct((M, n), jnp.float32, sharding=one_chip)
    if kernel == "gram_row":
        p = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
        c = _compile(lambda x, p: gram_row_pallas(
            x, p, anchor_first=True, block_n=2048, interpret=False), x, p)
    else:
        cf = jax.ShapeDtypeStruct((M,), jnp.float32, sharding=one_chip)
        c = _compile(lambda x, cf: combine_pallas(
            x, cf, block_n=2048, interpret=False), x, cf)
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < M * n * 4 // 8


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", ["gram_row", "gram", "combine"])
def test_arena_kernel_compiles_at_whisper_size(one_chip, whisper_bucket,
                                               kernel, dtype):
    """The three segmented arena kernels over a bucket of whisper-base's
    real block count, buffer stored at snapshot_rows: the compiler accepts
    the blocks, and no call copies the buffer (temporary memory stays a
    small fraction of it; a buffer whose rows are off the sublane tile,
    or padded per call, is copied whole)."""
    nb, n_sys, bn = whisper_bucket
    dt = jnp.dtype(dtype)
    rows = ka.snapshot_rows(M, dt)
    bs = _block_sys(nb, n_sys)
    x = jax.ShapeDtypeStruct((nb, rows, bn), dt, sharding=one_chip)
    if kernel == "gram_row":
        q = jax.ShapeDtypeStruct((nb, bn), dt, sharding=one_chip)
        c = _compile(lambda x, q: ka.gram_row_pallas(
            x, q, bs, n_sys, anchor_first=True, block_n=bn, m=M,
            interpret=False), x, q)
    elif kernel == "gram":
        c = _compile(lambda x: ka.gram_pallas(
            x, bs, n_sys, anchor_first=True, block_n=bn, m=M,
            interpret=False), x)
    else:
        cf = jax.ShapeDtypeStruct((n_sys, M), jnp.float32, sharding=one_chip)
        c = _compile(lambda x, cf: ka.combine_pallas(
            x, cf, bs, block_n=bn, interpret=False), x, cf)
    assert "tpu_custom_call" in c.as_text()
    buf_bytes = nb * rows * bn * dt.itemsize
    assert c.memory_analysis().temp_size_in_bytes < buf_bytes // 8


def test_arena_shard_map_gram_row_compiles_on_2x2(topo, whisper_bucket):
    """The lane-sharded arena row pass on a described 2x2 (data x model)
    mesh: one Pallas kernel per shard plus the O(n_sys*m) psum, and no
    all-gather of the buffer."""
    from repro.launch.mesh import make_mesh

    nb, n_sys, bn = whisper_bucket
    nb -= nb % 4                       # shard boundaries on block boundaries
    rows = ka.snapshot_rows(M, jnp.float32)
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    axes = ("data", "model")
    bs = _block_sys(nb // 4, n_sys)
    x = jax.ShapeDtypeStruct((nb, rows, bn), jnp.float32,
                             sharding=NamedSharding(mesh, P(axes)))
    q = jax.ShapeDtypeStruct((nb, bn), jnp.float32,
                             sharding=NamedSharding(mesh, P(axes)))
    c = _compile(lambda x, q: ka.gram_row(
        x, q, bs, n_sys, anchor_first=True, block_n=bn, m=M, mesh=mesh,
        lane_axes=axes, interpret=False), x, q)
    hlo = c.as_text()
    assert "tpu_custom_call" in hlo and "all-reduce" in hlo
    assert "all-gather" not in hlo
