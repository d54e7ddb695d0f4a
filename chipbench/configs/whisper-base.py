"""whisper-base as the benchmark runs it: the program's configuration,
checked against ``whisper-base.json``, and a plain float32 reference of the
forward pass and loss.

The reference follows the architecture the configuration states (see the
JSON's ``departures_from_published``): pre-LayerNorm blocks, 8-head
attention with no biases, a tanh-GELU MLP, learned positions, token
embeddings scaled by sqrt(d_model), cross-attention to the encoder output,
and a head tied to the token embedding over the real vocabulary. It uses
nothing of the program.
"""
from __future__ import annotations

import dataclasses
import math


def build(config: dict, traffic: dict, devices):
    """(acfg, model, mesh) through the launcher's own ``build``, with the
    published 448 decoder positions, the stated DMD schedule and the
    validation-gated controller; every key of the JSON is checked against
    what the program will run."""
    from repro.configs import DMDControllerConfig
    from repro.launch.train import build as launch_build
    from repro.models.transformer import LanguageModel

    acfg, model, mesh = launch_build(
        config["program_arch"], steps=10 ** 6,
        global_batch=traffic["global_batch"], seq=traffic["seq_len"],
        dmd=traffic["dmd"], devices=devices)
    changed = {k: v for k, v in config["model"].items()
               if getattr(acfg.model, k) != v}
    allowed = set(config["reduced"]) | set(config.get("changes", {}))
    if set(changed) - allowed:
        raise ValueError(f"keys changed from the program's configuration "
                         f"but not listed in reduced or changes: "
                         f"{sorted(set(changed) - allowed)}")
    mc = dataclasses.replace(acfg.model, **changed)
    d = config["dmd"]
    ctrl = d["controller"]
    dmd = dataclasses.replace(
        acfg.dmd, warmup_steps=d["warmup_steps"],
        controller=DMDControllerConfig(enabled=ctrl["enabled"],
                                       val_gate=ctrl["val_gate"]))
    acfg = dataclasses.replace(acfg, model=mc, dmd=dmd)
    model = LanguageModel(mc, head_tp=model.head_tp, chunk_k=model.chunk_k,
                          remat=model.remat, pad_heads_to=model.pad_heads_to)
    check_matches(config, acfg)
    return acfg, model, mesh


def check_matches(config: dict, acfg) -> None:
    """Raise if the program would run other sizes than the JSON states."""
    bad = []
    for group, obj in (("model", acfg.model), ("optimizer", acfg.optimizer),
                       ("dmd", acfg.dmd)):
        for k, v in config[group].items():
            got = getattr(obj, k)
            if isinstance(v, dict):
                got = {kk: _plain(getattr(got, kk)) for kk in v}
            if got != v:
                bad.append(f"{group}.{k}: stated {v!r}, program {got!r}")
    if bad:
        raise ValueError("configuration mismatch: " + "; ".join(bad))


def _plain(v):
    """A tuple as the JSON states it, a list."""
    return list(v) if isinstance(v, tuple) else v


def weight_rule(path: str, shape: tuple) -> str:
    """LayerNorm scales start at one and shifts at zero; every matrix,
    embedding and position table is normal with std 1/sqrt(rows)."""
    if path.endswith("['scale']"):
        return "ones"
    if path.endswith("['b']"):
        return "zeros"
    return "fan_in"


def traffic_shape(config: dict, traffic: dict) -> dict:
    mc = config["model"]
    return {"batch": traffic["global_batch"], "seq": traffic["seq_len"],
            "vocab": mc["vocab_size"],
            "frames": (mc["encoder_seq_len"], mc["d_model"])}


def tokens_per_step(config: dict, traffic: dict) -> int:
    """Decoder target tokens trained per step."""
    return traffic["global_batch"] * traffic["seq_len"]


def flops_per_step(config: dict, traffic: dict) -> float:
    from bench.counts import encdec_train_flops
    mc = config["model"]
    return encdec_train_flops(
        batch=traffic["global_batch"], seq=traffic["seq_len"],
        frames=mc["encoder_seq_len"], d_model=mc["d_model"],
        d_ff=mc["d_ff"], vocab=mc["vocab_size"],
        enc_layers=mc["n_encoder_layers"], dec_layers=mc["n_layers"])


def reference_loss(config: dict, cast=None):
    """``loss(params, batch)`` in float32 at full matmul precision.
    ``cast`` (default: none) rounds every matmul operand first, to compute
    the same function at a lower precision."""
    import jax
    import jax.numpy as jnp

    mc = config["model"]
    H, hd, V = mc["n_heads"], mc["head_dim"], mc["vocab_size"]
    hi = jax.lax.Precision.HIGHEST
    rnd = cast or (lambda x: x)

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=hi)

    def ein(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b), precision=hi)

    def ln(x, q):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * q["scale"] + q["b"]

    def attention(xq, xkv, q, causal):
        B, Sq, _ = xq.shape
        Sk = xkv.shape[1]
        Q = mm(xq, q["wq"]).reshape(B, Sq, H, hd)
        K = mm(xkv, q["wk"]).reshape(B, Sk, H, hd)
        Vv = mm(xkv, q["wv"]).reshape(B, Sk, H, hd)
        s = ein("bqhd,bkhd->bhqk", Q, K) / math.sqrt(hd)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((Sq, Sk), bool)), s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = ein("bhqk,bkhd->bqhd", a, Vv).reshape(B, Sq, H * hd)
        return mm(o, q["wo"])

    def mlp(x, q):
        return mm(jax.nn.gelu(mm(x, q["w_in"]), approximate=True),
                  q["w_out"])

    def layer(p, i):
        return jax.tree_util.tree_map(lambda a: a[i], p)

    @jax.checkpoint
    def enc_layer(x, q):
        x = x + attention(ln(x, q["ln1"]), ln(x, q["ln1"]), q["attn"], False)
        return x + mlp(ln(x, q["ln2"]), q["mlp"])

    @jax.checkpoint
    def dec_layer(y, e, q):
        h = ln(y, q["ln1"])
        y = y + attention(h, h, q["self_attn"], True)
        y = y + attention(ln(y, q["ln_x"]), e, q["cross_attn"], False)
        return y + mlp(ln(y, q["ln2"]), q["mlp"])

    def loss(p, batch):
        frames, tokens = batch["frames"], batch["tokens"]
        x = frames + p["enc_pos_emb"][None, :frames.shape[1]]
        for i in range(mc["n_encoder_layers"]):
            x = enc_layer(x, layer(p["seg0"], i))
        S = tokens.shape[1]
        y = p["emb"][tokens] * math.sqrt(mc["d_model"]) + p["pos_emb"][None, :S]
        for i in range(mc["n_layers"]):
            y = dec_layer(y, x, layer(p["seg1"], i))
        y = ln(y, p["final_norm"])
        logits = mm(y, p["emb"][:V].T)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, batch["labels"][..., None],
                                     axis=-1)[..., 0]
        return jnp.mean(lse - picked)

    return loss
