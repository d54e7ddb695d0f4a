"""Configuration dataclasses for the repro framework.

Plain frozen dataclasses (no pydantic dependency in the hot path): a config is
a *value*, hashable where possible, so jitted step functions can close over it
as a static argument.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Tuple

from repro.core.schedule import DMDGroupRule


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 1
    expert_d_ff: int = 0
    n_shared_experts: int = 0       # shared (always-on) experts, llama4-style
    shared_d_ff: int = 0
    moe_every: int = 1              # 1 = every layer is MoE; 2 = alternate dense/MoE
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    aux_loss_weight: float = 0.01
    # weight-stationary (default): expert weights FSDP over "data" on the
    # model dim -> re-gathered every use. activation-stationary: expert
    # weights FSDP over their ffn dim (stay resident); the (much smaller)
    # dispatched activations all-gather instead. See §Perf hillclimb #1.
    weight_stationary: bool = True


@dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 0              # N (SSD state size per head)
    head_dim: int = 64              # P
    conv_width: int = 4
    expand: int = 2                 # d_inner = expand * d_model
    n_groups: int = 1               # B/C groups (Mamba-2)
    chunk: int = 256                # SSD chunk length


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"           # dense|moe|ssm|hybrid|encdec|vlm
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 2
    n_kv_heads: int = 2
    head_dim: int = 64
    d_ff: int = 256
    vocab_size: int = 256
    act: str = "silu"               # silu (swiglu) | gelu (geglu) | gelu_mlp | softsign
    norm: str = "rms"               # rms | ln
    rope_theta: float = 10000.0
    mrope_sections: Tuple[int, ...] = ()   # qwen2-vl M-RoPE (t,h,w) split of head_dim/2
    sliding_window: int = 0          # 0 = full attention
    global_every: int = 0            # gemma3: every k-th layer is global, rest local
    tie_embeddings: bool = True
    max_seq_len: int = 8192
    moe: MoEConfig = field(default_factory=MoEConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    # hybrid (zamba2): one shared attention block applied every `shared_attn_every`
    # ssm layers.
    shared_attn_every: int = 0
    # enc-dec (whisper)
    n_encoder_layers: int = 0
    encoder_seq_len: int = 0         # fixed frame count from the (stubbed) frontend
    learned_pos_emb: bool = False
    # vlm / audio stub: inputs are precomputed embeddings rather than token ids
    frontend_stub: bool = False
    dtype: str = "bfloat16"          # activation/param compute dtype
    logit_softcap: float = 0.0       # gemma-style final-logit softcapping
    vocab_pad_to: int = 16           # Megatron-style vocab padding for TP

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return -(-self.vocab_size // p) * p if p else self.vocab_size

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def is_moe_layer(self, idx: int) -> bool:
        m = self.moe
        return m.n_experts > 0 and (idx % m.moe_every == m.moe_every - 1)

    def is_global_attn_layer(self, idx: int) -> bool:
        """gemma3 5:1 pattern: layer idx is global iff (idx+1) % global_every == 0."""
        if self.global_every <= 0:
            return self.sliding_window == 0
        return (idx + 1) % self.global_every == 0


# ---------------------------------------------------------------------------
# DMD (the paper's technique)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DMDControllerConfig:
    """Loss-gated adaptive jump controller (DESIGN.md §5).

    The paper tunes the number of backprop steps per DMD estimation by hand;
    the controller closes that loop: every jump is gated on a held-out
    microbatch loss evaluated inside the jitted DMD step (accept / halve the
    effective relax and re-blend / reject with bit-exact rollback), and the
    per-group accept history adapts the effective horizon ``s_g`` and the
    POD truncation. ``enabled=False`` (the default) is bit-exact with the
    ungated schedule — no gate forward, no controller state in TrainState.
    """
    enabled: bool = False
    eval_rows: int = 32             # held-out microbatch rows for the gate
                                    # (0 = use the full eval batch; clamped
                                    # to the actual eval-batch size — never
                                    # slices past it)
    accept_tol: float = 1e-3        # accept iff loss_post <= loss_pre *
                                    # (1 + accept_tol). The old 0.0 default
                                    # rejected noise-level TIES: with small
                                    # eval_rows the gate loss carries fp32
                                    # sampling noise and a jump that changed
                                    # nothing real flapped to REJECT. A small
                                    # positive tol tolerates noise-level
                                    # regressions (ISSUE 9).
    val_gate: bool = False          # gate on the trainer's persistent
                                    # validation split (disjoint from the
                                    # training stream) even when the caller
                                    # hands fit() an eval_batch. False keeps
                                    # the caller's batch — the PR-8 pinned
                                    # path. Either way the gate NEVER falls
                                    # back to drawing from the training
                                    # iterator (train/loop.py).
    grow: float = 1.5               # s_eff multiplier on consecutive full
                                    # accepts (capped at the group's s)
    shrink: float = 0.5             # s_eff multiplier on a rejected jump
    s_min: float = 1.0              # lower bound for the adapted horizon
    relax_floor: float = 0.125      # lower bound for the effective relax
                                    # scale (scaled down on every scale-back)
    gain_ema: float = 0.8           # EMA decay of the per-jump relative gain
                                    # (loss_pre - loss_final) / loss_pre
    energy: float = 0.995           # target cumulative-energy fraction for
                                    # the POD rank (replaces the global tol
                                    # noise floor while the controller is on;
                                    # per-group override: DMDGroupRule.energy)
    ridge: float = 0.0              # base Tikhonov shrinkage of the jump
                                    # solve, RELATIVE to sigma_max^2
                                    # (core/dmd.py::_ridge_inv_sigma);
                                    # 0 = the bit-exact legacy solve.
                                    # Per-group override: DMDGroupRule.ridge.
    ridge_max: float = 0.1          # clamp for the meta-tuned per-group
                                    # ridge_eff (controller state)
    shrink_levels: Tuple[float, ...] = (0.5,)
                                    # SCALED-branch relax line search: blend
                                    # fractions tried in order (each blends
                                    # level*jump + (1-level)*current) after a
                                    # rejected full jump. The default (0.5,)
                                    # is the PR-4 single blind halving —
                                    # bit-exact with the PR-8 gated path.
    meta_lr: float = 0.0            # > 0 (matpow mode only): after each gate
                                    # round, backprop the gate-batch loss
                                    # through the differentiable jump and EMA
                                    # each jumped group's relax/ridge knobs
                                    # toward the descent direction (Weiner &
                                    # Semaan, PAPERS.md). 0 = off (bit-exact).


@dataclass(frozen=True)
class DMDConfig:
    enabled: bool = True
    m: int = 14                     # snapshots per DMD round (paper: 14)
    s: int = 55                     # extrapolation horizon in steps (paper: 55)
    tol: float = 1e-4               # singular-value filter sigma_r/sigma_0 > tol
                                    # (paper: 1e-10 with float64; 1e-4 is the
                                    # fp32 Gram noise floor — see dmd.py)
    atol: float = 0.0               # ABSOLUTE sigma floor joined to the
                                    # relative tol/energy mask (pymor-style
                                    # atol/rtol truncation, dmd.py); 0 = off
    warmup_steps: int = 100         # plain steps before the first snapshot window
    cooldown_steps: int = 10        # unrecorded steps after each jump: lets the
                                    # optimizer moments re-adapt so the next
                                    # window measures clean dynamics
    mode: str = "matpow"            # matpow (TPU-native) | eig (host callback)
    clamp_eigs: bool = False        # eig mode only: |lambda| <- min(|lambda|, 1)
    anchor: str = "first"           # none (paper) | first | mean; see dmd.py
    affine: bool = True             # affine-augmented DMD (rank-one Gram update)
    trust_region: float = 2.0       # cap jump at tr*s*rms_step; 0 = off (paper)
    relax: float = 1.0              # w <- (1-relax) w_m + relax * w_dmd
    snapshot_dtype: str = "float32" # fp32 | bfloat16 snapshot storage
    gram_upcast: bool = True        # False: stream bf16 with f32 accumulation
                                    # (halves DMD jump bandwidth; see §Perf)
    streaming_gram: bool = True     # maintain the (stack..., m, m) Gram
                                    # incrementally in TrainState: one O(m*n)
                                    # row pass per record fused into the
                                    # train step, so `apply` is pure O(m^3)
                                    # algebra + one combine pass. False =
                                    # seed behavior (full O(m^2*n) recompute
                                    # at every apply), kept as the A/B
                                    # baseline and correctness oracle.
                                    # Requires anchor in {none, first}.
    arena: bool = True              # pack compatible leaves (same schedule
                                    # group / dtype / sharding class) into
                                    # contiguous per-bucket arenas: ONE
                                    # segmented kernel launch and ONE batched
                                    # coefficient solve per group instead of
                                    # one per leaf (core/arena.py,
                                    # DESIGN.md §7). False = the per-leaf
                                    # route everywhere — the bit-exact A/B
                                    # oracle.
    arena_block_n: int = 512        # arena segment quantum / kernel n-tile
                                    # cap (rounded to 128-lane multiples and
                                    # clamped to the bucket's widest member);
                                    # every segment is padded to a multiple
                                    # so kernel blocks never straddle leaves
    arena_native: bool = True       # arena-native parameter residency
                                    # (DESIGN.md §7): during Trainer.fit the
                                    # managed params of packed leaves live IN
                                    # the bucket's contiguous device buffer;
                                    # the forward reads zero-copy slice views
                                    # and record is one dynamic_update_slice
                                    # per bucket instead of a pack-copy
                                    # gather. False = the PR-5 pack-copy
                                    # route — the bit-exact A/B oracle.
                                    # Residency only engages for optimizers
                                    # whose moments are elementwise
                                    # (train/step.py::RESIDENT_OPTIMIZERS).
    scope: str = "leaf"             # leaf | bucket — the DMD system
                                    # granularity (DESIGN.md §9). "leaf"
                                    # (default) fits one operator per system
                                    # (one per leaf / stacked layer) — the
                                    # bit-exact legacy route. "bucket" fits
                                    # ONE shared Koopman operator per arena
                                    # bucket over the concatenated bucket
                                    # state: the bucket Gram is the
                                    # segment-SUM of the per-system Grams
                                    # (pad lanes are zero, every segment
                                    # shares the bucket's slot schedule, so
                                    # the sum IS the concatenated-state
                                    # Gram), the jump solves n_buckets
                                    # systems per group instead of n_leaves
                                    # (eig host-callback batches shrink
                                    # identically), and the combine
                                    # broadcasts one coefficient vector per
                                    # bucket. Cross-layer modes become
                                    # expressible (Turjeman et al.;
                                    # Manojlović et al., PAPERS.md).
                                    # System-sharded buckets (sys_axes) stay
                                    # per-system either way — collapsing
                                    # them would need a cross-shard psum
                                    # over the stack axis. Checkpoints stay
                                    # leaf-wise on disk in both scopes.
    kernel_route: str = "auto"      # auto | pallas_flat | pallas_shard_map |
                                    # dot_general: force the per-leaf kernel
                                    # route in core/leafplan.py. "auto" picks
                                    # per leaf (flat unsharded -> pallas_flat,
                                    # stacked/sharded -> pallas_shard_map).
                                    # A forced pallas_flat only applies where
                                    # flattening is safe (unstacked,
                                    # unsharded); other leaves keep the auto
                                    # choice. See DESIGN.md §3.
    param_filter: str = "all"       # all | non_expert | matrices_only
                                    # (legacy strings — mapped onto exclusion
                                    # group rules by core/schedule.py)
    min_param_size: int = 0         # skip leaves smaller than this many elements
    groups: Tuple[DMDGroupRule, ...] = ()
                                    # per-leaf schedule groups (DESIGN.md §4):
                                    # each rule's structural matcher (path
                                    # regex / ndim / size) either excludes
                                    # matching leaves or gives them their own
                                    # (m, s, warmup, cooldown, relax, anneal,
                                    # phase) schedule; unset fields inherit
                                    # the globals above, which form the
                                    # default group 0. First match wins.
                                    # Phase offsets stagger jumps across
                                    # groups (at most one group's jump spike
                                    # per step instead of every leaf at once).
    anneal: float = 1.0             # multiplicative decay of `relax` per DMD round
    controller: DMDControllerConfig = field(
        default_factory=DMDControllerConfig)
                                    # loss-gated adaptive jump controller
                                    # (core/controller.py, DESIGN.md §5):
                                    # accept / scale-back / reject-with-
                                    # rollback gate on a held-out microbatch,
                                    # auto-tuned per-group horizons, energy-
                                    # based POD rank. Off by default (bit-
                                    # exact with the ungated schedule).
    reset_opt_state: bool = True    # reset Adam moments after a DMD jump (the
                                    # jump teleports weights; stale moments
                                    # poison the next window's dynamics).
                                    # Per-group override: DMDGroupRule.
                                    # reset_opt — with staggered groups only
                                    # the JUMPED groups' moments reset, and
                                    # slow groups (norms/biases) usually opt
                                    # out entirely (DESIGN.md §4).


# ---------------------------------------------------------------------------
# Optimizer / schedule
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adam"              # sgd|momentum|adam|adamw|adafactor|adam8bit
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 0.0          # 0 = off; else global-norm clip
    schedule: str = "constant"      # constant|cosine|wsd|linear_warmup
    warmup_steps: int = 0
    total_steps: int = 10000
    decay_fraction: float = 0.1     # WSD: fraction of total steps in decay phase
    min_lr_ratio: float = 0.1


# ---------------------------------------------------------------------------
# Parallelism / runtime
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParallelConfig:
    grad_accum: int = 1              # microbatch accumulation factor
    remat: str = "none"              # none | block | full
    zero1_over_pod: bool = False     # shard optimizer state over pod axis
    grad_compression: str = "none"   # none | int8 (cross-pod quantized all-reduce)
    scan_layers: bool = True         # lax.scan over layer stacks
    # serving
    kv_seq_shard_threshold: int = 16 # shard KV by kv-head if n_kv >= this else by seq


@dataclass(frozen=True)
class TrainConfig:
    seed: int = 0
    global_batch: int = 8
    seq_len: int = 128
    steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 0        # 0 = off
    checkpoint_dir: str = ""
    keep_checkpoints: int = 3


@dataclass(frozen=True)
class ShapeConfig:
    """One dry-run cell: (kind, seq_len, global_batch)."""
    name: str = "train_4k"
    kind: str = "train"              # train | prefill | decode
    seq_len: int = 4096
    global_batch: int = 256


STANDARD_SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", "train", 4096, 256),
    ShapeConfig("prefill_32k", "prefill", 32768, 32),
    ShapeConfig("decode_32k", "decode", 32768, 128),
    ShapeConfig("long_500k", "decode", 524288, 1),
)


@dataclass(frozen=True)
class ArchConfig:
    """Top-level bundle: everything needed to build + run one architecture."""
    model: ModelConfig
    dmd: DMDConfig = field(default_factory=DMDConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    # which standard shapes apply; names from STANDARD_SHAPES
    shapes: Tuple[str, ...] = ("train_4k", "prefill_32k", "decode_32k")
    skip_notes: str = ""

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


def reduced(model: ModelConfig, **overrides) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests."""
    shrink = dict(
        n_layers=min(model.n_layers, 4),
        d_model=min(model.d_model, 64),
        n_heads=min(model.n_heads, 4),
        n_kv_heads=min(model.n_kv_heads, 2),
        head_dim=min(model.head_dim, 16),
        d_ff=min(model.d_ff, 128),
        vocab_size=min(model.vocab_size, 512),
        max_seq_len=min(model.max_seq_len, 256),
    )
    if model.n_kv_heads == model.n_heads:       # keep MHA shape relation
        shrink["n_kv_heads"] = shrink["n_heads"]
    if model.n_kv_heads == 1:
        shrink["n_kv_heads"] = 1
    if model.moe.n_experts > 0:
        shrink["moe"] = dataclasses.replace(
            model.moe, n_experts=min(model.moe.n_experts, 8),
            top_k=min(model.moe.top_k, 2),
            expert_d_ff=min(model.moe.expert_d_ff, 64),
            shared_d_ff=min(model.moe.shared_d_ff, 64),
        )
    if model.ssm.state_dim > 0:
        shrink["ssm"] = dataclasses.replace(
            model.ssm, state_dim=min(model.ssm.state_dim, 16),
            head_dim=min(model.ssm.head_dim, 16), chunk=32)
    if model.n_encoder_layers > 0:
        shrink["n_encoder_layers"] = min(model.n_encoder_layers, 2)
        shrink["encoder_seq_len"] = min(model.encoder_seq_len, 32)
    if model.global_every > 0:
        shrink["n_layers"] = max(shrink["n_layers"], model.global_every)
    if model.shared_attn_every > 0:
        shrink["n_layers"] = max(shrink["n_layers"], model.shared_attn_every)
    if model.sliding_window > 0:
        shrink["sliding_window"] = min(model.sliding_window, 32)
    if model.mrope_sections:
        hd = shrink.get("head_dim", model.head_dim)
        s1 = max(hd // 8, 1)
        rest = hd // 2 - s1
        shrink["mrope_sections"] = (s1, rest // 2, rest - rest // 2)
    shrink.update(overrides)
    return dataclasses.replace(model, **shrink)
