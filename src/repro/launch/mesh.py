"""Mesh construction (FUNCTIONS — importing this module never touches jax
device state).

Every mesh in the tree is built through ``make_mesh``: ``jax.make_mesh``
defaults to ``Explicit`` axes, under which sharding-in-types rejects the
snapshot record's ``dynamic_update_slice`` (operand and update shardings
differ). The codebase is written for GSPMD propagation, so every axis is
``Auto``."""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for_devices(n_devices: int, model_parallel: int = 1,
                          pods: int = 1, *, devices=None):
    """Elastic helper: build a (pod, data, model) mesh from whatever device
    count is available (restart-after-resize path, and the launcher's mesh
    on a single host)."""
    assert n_devices % (model_parallel * pods) == 0, \
        f"{n_devices} devices not divisible by tp={model_parallel} x pods={pods}"
    data = n_devices // (model_parallel * pods)
    if pods > 1:
        return make_mesh((pods, data, model_parallel),
                         ("pod", "data", "model"), devices=devices)
    return make_mesh((data, model_parallel), ("data", "model"),
                     devices=devices)
