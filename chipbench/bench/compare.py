"""The numbers that decide ``correct``: gaps between what the program
produced and what the reference computed, each read against a limit."""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import median
from typing import List, Sequence


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def rel_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    """Largest |p - r| / |r| over paired readings (nan if any is not
    finite)."""
    out = 0.0
    for p, r in zip(prog, ref):
        if not (math.isfinite(p) and math.isfinite(r)):
            return math.nan
        out = max(out, abs(p - r) / max(abs(r), 1e-30))
    return out


def norm_gap(prog: Sequence[float], ref: Sequence[float],
             keep: Sequence[bool] = ()) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    read against the larger of that leaf's reference norm and the median
    leaf's (some leaves are all but zero)."""
    keep = list(keep) or [True] * len(ref)
    pairs = [(p, r) for p, r, k in zip(prog, ref, keep) if k]
    if not pairs:
        return math.nan
    med = median(r for _, r in pairs)
    out = 0.0
    for p, r in pairs:
        if not (math.isfinite(p) and math.isfinite(r)):
            return math.nan
        out = max(out, abs(p - r) / max(r, med, 1e-30))
    return out


def moving_leaves(ref_grad_norms: Sequence[float],
                  share: float = 1e-3) -> List[bool]:
    """Leaves whose reference gradient is at least ``share`` of the median
    leaf's: the others move under Adam by round-off alone and are left out
    of the parameter-change comparison."""
    med = median(ref_grad_norms)
    return [g >= share * med for g in ref_grad_norms]
