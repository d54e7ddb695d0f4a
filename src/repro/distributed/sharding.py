"""Logical-axis sharding rules: param-path regex -> PartitionSpec.

The mesh has physical axes ("pod", "data", "model") (pod optional). Logical
mapping (see DESIGN.md §6):
  * batch            -> ("pod", "data")      activations
  * tensor-parallel  -> "model"              heads / ffn hidden / vocab / experts
  * fsdp             -> "data"               the non-TP dim of every >=2D param
  * pod              -> pure DP (params replicated; optimizer state may add
                        "pod" sharding via ZeRO-1 flag)

Specs are derived from the param path name + trailing dims, so stacked
(scan-over-layers) leading dims are automatically replicated. A contextvar
mesh makes `constrain` a no-op on plain CPU tests (no mesh active), so model
code can sprinkle constraints unconditionally.
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "repro_mesh", default=None)


@contextlib.contextmanager
def mesh_context(mesh: Mesh):
    token = _MESH.set(mesh)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _MESH.reset(token)


def current_mesh() -> Optional[Mesh]:
    return _MESH.get()


def batch_axes(mesh: Optional[Mesh] = None):
    mesh = mesh or current_mesh()
    if mesh is not None and "pod" in mesh.axis_names:
        return ("pod", "data")
    return ("data",)


def constrain(x, *spec):
    """with_sharding_constraint if a mesh is active, else identity.

    Spec entries may be logical names: "batch" expands to ("pod","data") when
    the pod axis exists.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    resolved = tuple(batch_axes(mesh) if s == "batch" else s for s in spec)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


# ---------------------------------------------------------------------------
# Param partition rules
# ---------------------------------------------------------------------------
# Each rule: (path regex, spec for the TRAILING dims). Leading (stack) dims
# are padded with None. "fsdp" -> "data", "tp" -> "model".
_RULES = [
    # embeddings: (vocab, d_model) — vocab on TP, d on FSDP
    (r"(^|/)(emb|lm_head)$", ("tp", "fsdp")),
    (r"pos_emb$", (None, "fsdp")),
    # attention projections
    (r"wqkv$", ("fsdp", "tp")),
    (r"w[qkv]$", ("fsdp", "tp")),
    (r"wo$", ("tp", "fsdp")),
    # mlp
    (r"w_(gate|in)$", ("fsdp", "tp")),
    (r"w_out$", ("tp", "fsdp")),
    # moe experts: (E, d, f) / (E, f, d) — experts on TP (EP), d on FSDP
    (r"experts_(gate|in)$", ("tp", "fsdp", None)),
    (r"experts_out$", ("tp", None, "fsdp")),
    (r"router$", ("fsdp", None)),
    # mamba (split per-component projections — see models/ssm.py)
    (r"in_proj/(z|x|dt)$", ("fsdp", "tp")),
    (r"in_proj/(B|C)$", ("fsdp", None)),
    (r"out_proj$", ("tp", "fsdp")),
    (r"conv_w/x$", (None, "tp")),
    (r"conv_w/(B|C)$", None),
    (r"(A_log|dt_bias|skip_d)$", ("tp",)),
    # small vectors / scalars: replicated
    (r"(scale|bias|b)$", None),
]


def normalize_path(keystr: str) -> str:
    """jax keystr "['a']['b'].k" -> "/a/b/k" for regex rules."""
    s = re.sub(r"\['([^']+)'\]", r"/\1", keystr)
    s = s.replace(".", "/").replace("[", "/").replace("]", "")
    return s


def rule_for_path(path: str):
    """Raw logical trailing-dims rule for a param path (or None)."""
    path = normalize_path(path)
    for pattern, trailing in _RULE_OVERRIDES + _RULES:
        if re.search(pattern, path):
            return trailing
    return None


def resolve_rule(trailing, ndim: int, shape, mesh: Optional[Mesh]) -> P:
    """Logical trailing rule -> physical PartitionSpec with divisibility."""
    mesh = mesh or current_mesh()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh else {}

    def physical(logical, dim_size):
        ax = {"tp": "model", "fsdp": "data"}.get(logical, logical)
        if ax is None:
            return None
        size = axis_sizes.get(ax, 1)
        if dim_size is not None and size > 1 and dim_size % size != 0:
            return None
        return ax

    if trailing is None:
        return P()
    trailing = trailing[-ndim:] if ndim < len(trailing) else trailing
    pad = (None,) * (ndim - len(trailing))
    dims = list(shape[-len(trailing):]) if shape is not None \
        else [None] * len(trailing)
    resolved = tuple(physical(t, d) for t, d in zip(trailing, dims))
    return P(*(pad + resolved))


_RULE_OVERRIDES: list = []


def set_rule_overrides(overrides):
    """Prepend (regex, trailing-rule) pairs to the param rules — the per-arch
    sharding-strategy knob used by the §Perf hillclimbs (e.g. llama4's
    activation-stationary MoE)."""
    global _RULE_OVERRIDES
    _RULE_OVERRIDES = list(overrides or [])


def spec_for_path(path: str, ndim: int, mesh: Optional[Mesh] = None,
                  shape=None) -> P:
    """Map a param path + shape to a PartitionSpec (physical axis names)."""
    path = normalize_path(path)
    mesh = mesh or current_mesh()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh else {}

    def physical(logical, dim_size):
        ax = {"tp": "model", "fsdp": "data"}.get(logical, logical)
        if ax is None:
            return None
        size = axis_sizes.get(ax, 1)
        if dim_size is not None and size > 1 and dim_size % size != 0:
            return None                      # indivisible -> replicate
        return ax

    for pattern, trailing in _RULE_OVERRIDES + _RULES:
        if re.search(pattern, path):
            if trailing is None:
                return P()
            trailing = trailing[-ndim:] if ndim < len(trailing) else trailing
            pad = (None,) * (ndim - len(trailing))
            dims = list(shape[-len(trailing):]) if shape is not None \
                else [None] * len(trailing)
            resolved = tuple(physical(t, d) for t, d in zip(trailing, dims))
            return P(*(pad + resolved))
    return P()                               # default: replicated


def partition_specs(params: Any, mesh: Optional[Mesh] = None) -> Any:
    """Pytree of PartitionSpecs matching `params` (arrays or ShapeDtypeStructs)."""
    def one(path, leaf):
        return spec_for_path(jax.tree_util.keystr(path), leaf.ndim,
                             mesh, leaf.shape)
    return jax.tree_util.tree_map_with_path(one, params)


def logical_axis_rules():
    return {"tp": "model", "fsdp": "data", "batch": ("pod", "data")}


def named_shardings(params: Any, mesh: Mesh) -> Any:
    specs = partition_specs(params, mesh)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))
