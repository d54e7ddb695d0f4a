"""The paper's pollutant MLP as the benchmark runs it: the program's
configuration, checked against ``pollutant-mlp.json``, and a plain float64
numpy reference of the network, its MSE loss and gradient under Adam.

The reference uses nothing of the program. ``cast`` rounds every matmul
operand, activation and stored parameter to a lower precision, to compute
the same steps as a control.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def build(config: dict, traffic: dict):
    """(acfg, model) for the program, DMD on or off as the traffic says."""
    from repro.configs import get_config
    from repro.models.mlp_net import MLPModel

    acfg = get_config(config["program_arch"])
    if not traffic["dmd"]:
        acfg = dataclasses.replace(
            acfg, dmd=dataclasses.replace(acfg.dmd, enabled=False))
    check_matches(config, acfg)
    model = MLPModel(tuple(config["model"]["sizes"]), config["model"]["act"])
    return acfg, model


def check_matches(config: dict, acfg) -> None:
    from repro.models.mlp_net import PAPER_SIZES
    bad = []
    if tuple(config["model"]["sizes"]) != tuple(PAPER_SIZES):
        bad.append(f"sizes {config['model']['sizes']} != {PAPER_SIZES}")
    if config["model"]["act"] != acfg.model.act:
        bad.append(f"act {acfg.model.act}")
    for group, obj in (("optimizer", acfg.optimizer), ("dmd", acfg.dmd)):
        for k, v in config[group].items():
            if getattr(obj, k) != v:
                bad.append(f"{group}.{k}: stated {v!r}, program "
                           f"{getattr(obj, k)!r}")
    if bad:
        raise ValueError("configuration mismatch: " + "; ".join(bad))


def weight_rule(path: str, shape: tuple) -> str:
    """Xavier-normal matrices and zero biases (paper §2)."""
    return "zeros" if path.endswith("['b']") else "xavier"


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------

def _identity(a):
    return a


def to_lists(params) -> list:
    """[(w, b), ...] float64 arrays from the program-shaped pytree."""
    return [(np.asarray(params[f"l{i}"]["w"], np.float64),
             np.asarray(params[f"l{i}"]["b"], np.float64))
            for i in range(len(params))]


def loss_and_grad(layers: list, x, y, cast=_identity):
    """MSE over every output and its gradient, by hand: softsign hidden
    layers, linear output."""
    hs, zs = [cast(x)], []
    h = cast(x)
    for i, (w, b) in enumerate(layers):
        z = cast(cast(h) @ cast(w) + b)
        zs.append(z)
        h = z if i == len(layers) - 1 else cast(z / (1.0 + np.abs(z)))
        hs.append(h)
    diff = h - y
    loss = float(np.mean(diff * diff))
    dz = cast(2.0 * diff / diff.size)
    grads = [None] * len(layers)
    for i in range(len(layers) - 1, -1, -1):
        w, _ = layers[i]
        grads[i] = (cast(cast(hs[i]).T @ dz), cast(dz.sum(axis=0)))
        if i:
            dh = cast(dz @ cast(w).T)
            dz = cast(dh / (1.0 + np.abs(zs[i - 1])) ** 2)
    return loss, grads


def adam_steps(layers: list, x, y, opt: dict, n: int, cast=_identity,
               after_step=None):
    """``n`` full-batch Adam steps from step 0. Returns the losses, the
    first gradient and the layers after the last step."""
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    m = [(np.zeros_like(w), np.zeros_like(b)) for w, b in layers]
    v = [(np.zeros_like(w), np.zeros_like(b)) for w, b in layers]
    layers = [(cast(w), cast(b)) for w, b in layers]
    losses, first = [], None
    for t in range(1, n + 1):
        loss, g = loss_and_grad(layers, x, y, cast)
        losses.append(loss)
        if first is None:
            first = g
        new = []
        for i, ((w, b), (gw, gb)) in enumerate(zip(layers, g)):
            out = []
            for j, (p, gp) in enumerate(((w, gw), (b, gb))):
                m[i][j][...] = b1 * m[i][j] + (1 - b1) * gp
                v[i][j][...] = b2 * v[i][j] + (1 - b2) * gp * gp
                u = lr * (m[i][j] / (1 - b1 ** t)) / (
                    np.sqrt(v[i][j] / (1 - b2 ** t)) + eps)
                out.append(cast(p - u))
            new.append(tuple(out))
        layers = new
        if after_step is not None:
            after_step(t - 1, layers)
    return losses, first, layers

