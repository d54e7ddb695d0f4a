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
import collections
import re

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


# --------------------------------------------------------------------------
# whisper-base's train step with the flash-attention kernels
# --------------------------------------------------------------------------

JNP_TRAIN_STEP_TEMP = 12_737_576_448    # the jnp core, one chip (whisper-base.json)


def _flash_runs(hlo: str) -> collections.Counter:
    """Executions a step of each flash-attention custom call of compiled
    HLO text, by (kernel, padded q length, padded k length). A call in a
    layer loop counts once per trip: the loop's condition compares its
    counter against one s32 constant."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
        elif cur is not None:
            cur.append(line)

    def trips(cond):
        n = re.findall(r"= s32\[\]\S* constant\((\d+)\)", "\n".join(comps[cond]))
        assert len(n) == 1, cond
        return int(n[0])

    runs = collections.Counter()

    def walk(comp, mult):
        for ins in comps[comp]:
            call = re.match(r"\s*(?:ROOT )?%flash_attention_(fwd|bwd)\S* = "
                            r".*operand_layout_constraints=\{\w+\[\d+,\d+,"
                            r"(\d+),\d+\]\S*, \w+\[\d+,\d+,(\d+),", ins)
            if call:
                kind, sq, sk = call.groups()
                runs[(kind, int(sq), int(sk))] += mult
            loop = re.search(r"condition=%([^,\s]+), body=%([^,\s]+)", ins)
            if loop:
                walk(loop.group(2), mult * trips(loop.group(1)))
            for sub in re.findall(r"(?:calls|branch_computations)=\{?"
                                  r"([^}=]*?)(?:\}|, \w+=|$)", ins):
                for name in re.findall(r"%([^,\s}]+)", sub):
                    walk(name, mult)

    walk(entry, 1)
    return runs


def _whisper_train_step(devices, model_parallel, monkeypatch):
    """whisper-base's resident DMD train step at published widths, batch 4
    x 448 tokens and 1500 frames, as the launcher builds it for
    ``devices``, compiled for them."""
    import dataclasses
    from repro.distributed.sharding import mesh_context
    from repro.launch.train import build
    from repro.models.transformer import LanguageModel
    from repro.train import Trainer
    from repro.train.state import TrainState
    from repro.train.step import state_resident

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    acfg, model, mesh = build("whisper-base", steps=10 ** 6, global_batch=4,
                              seq=448, devices=devices,
                              model_parallel=model_parallel)
    mc = dataclasses.replace(acfg.model, max_seq_len=448)
    acfg = dataclasses.replace(acfg, model=mc)
    model = LanguageModel(mc, head_tp=model.head_tp, chunk_k=model.chunk_k,
                          remat=model.remat, pad_heads_to=model.pad_heads_to)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 448), jnp.int32),
             "labels": jax.ShapeDtypeStruct((4, 448), jnp.int32),
             "frames": jax.ShapeDtypeStruct((4, 1500, 512), jnp.float32)}
    trainer = Trainer(model, acfg, mesh=mesh, val_batch=batch)
    acc = trainer.acc

    def fresh(params):
        bufs = acc.init(params)
        return TrainState(params, trainer.opt.init(params),
                          jnp.asarray(110, jnp.int32), bufs,
                          acc.init_grams(bufs), acc.init_controller())

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    with mesh_context(mesh):
        state = jax.eval_shape(fresh, params)
        state = jax.eval_shape(lambda s: state_resident(acc, acfg, s), state)
        return trainer.train_step.lower(
            state, batch, jax.ShapeDtypeStruct((), jnp.int32)).compile()


@pytest.mark.parametrize("chips", [1, 4])
def test_whisper_train_step_runs_flash_kernels(topo, monkeypatch, chips):
    """Every one of the 18 attentions (6 encoder self, 6 cross, 6 decoder
    self) runs the flash kernel's forward and backward, on one
    chip and, per shard under shard_map, on a 2x2 (data x model) mesh; and
    on one chip the step's temporaries fall below the jnp core's, whose
    saved fp32 scores they held."""
    c = _whisper_train_step(topo.devices[:chips], 2 if chips == 4 else 1,
                            monkeypatch)
    want = {(kind, sq, sk): 6 for kind in ("fwd", "bwd")
            for sq, sk in ((1536, 1536), (512, 1536), (512, 512))}
    assert dict(_flash_runs(c.as_text())) == want
    if chips == 1:
        assert c.memory_analysis().temp_size_in_bytes < JNP_TRAIN_STEP_TEMP


@pytest.mark.parametrize("arch,S,H,K,d,dtype", [
    ("qwen2-vl-7b", 4096, 0, 0, 0, "bfloat16"),   # the launcher's default S
    (None, 8192, 4, 4, 128, "bfloat16"),          # at the dQ VMEM budget
    (None, 8192, 4, 4, 80, "bfloat16"),           # zamba2's head dim
    (None, 4096, 4, 4, 256, "bfloat16"),
    (None, 5376, 4, 4, 128, "float32"),
])
def test_flash_kernel_compiles_up_to_its_vmem_bound(one_chip, arch, S, H, K,
                                                    d, dtype):
    """The flash kernel's forward and backward compile for a v5e at the
    longest query sequence `flash.fits` admits for the head dim and dtype
    (the backward holds dQ of the whole sequence in VMEM), and at a
    published GQA config's heads at S=4096; a block longer is refused by
    the route."""
    from repro.configs import get_config
    from repro.kernels import flash

    if arch:
        mc = get_config(arch).model
        H, K, d = mc.n_heads, mc.n_kv_heads, mc.head_dim
    dtype = jnp.dtype(dtype)
    assert flash.fits(S, d, dtype)
    if not arch:
        assert not flash.fits(S + flash.LANES, d, dtype)
    q = jax.ShapeDtypeStruct((1, S, H, d), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, K, d), dtype, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    hlo = _compile(jax.grad(loss, (0, 1, 2)), q, kv, kv).as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    for kind in ("fwd", "bwd"):
        assert any(f"flash_attention_{kind}" in ln for ln in calls), kind
