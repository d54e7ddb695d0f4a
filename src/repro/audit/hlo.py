"""Shared HLO-text analysis primitives — the ONE home for every regex the
repo runs over compiled HLO.

Before this module the donation audit (tests/test_donation.py), the
shard_map all-gather audits (tests/dist_worker.py) and the dry-run
collective inventory (launch/dryrun.py) each carried their own copy of
the shape/collective parsing; a dtype added to one byte map silently
missed the others. Everything textual now lives here; the audit passes
(repro.audit.passes) and those callers all import these helpers.

Conventions: shapes are matched as HLO shape strings (``f32[4,2,32]``);
``shape_str(leaf)`` renders a JAX leaf the same way so pytree leaves and
HLO operands compare directly.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Set, Tuple

# HLO dtype -> bytes/element (shared by every byte-accounting consumer)
DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
               "u32": 4, "u16": 2, "s16": 2, "s8": 1, "u8": 1, "pred": 1,
               "c64": 8, "c128": 16}

_JAX_DTYPE = {"float64": "f64", "float32": "f32", "bfloat16": "bf16",
              "float16": "f16", "int64": "s64", "int32": "s32",
              "uint32": "u32", "int16": "s16", "uint16": "u16",
              "int8": "s8", "uint8": "u8", "bool": "pred"}

_SHAPE_RE = re.compile(r"([a-z]+[0-9]+|pred)\[([0-9,]*)\]")

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")


def shape_str(leaf) -> str:
    """JAX leaf -> its HLO shape string (``bf16[4,2,32]``)."""
    d = _JAX_DTYPE.get(str(leaf.dtype), str(leaf.dtype))
    return d + "[" + ",".join(str(int(s)) for s in leaf.shape) + "]"


def shape_bytes(s: str) -> int:
    """Total bytes of one HLO shape string (0 if unparsable)."""
    m = _SHAPE_RE.match(s)
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(m.group(1), 4)


def alias_count(hlo: str) -> int:
    """Number of entries in the module's ``input_output_alias`` table
    (0 when the module has none — nothing was donated)."""
    for line in hlo.splitlines():
        if "input_output_alias" in line:
            return len(re.findall(r"\{\d+\}: \(\d+", line))
    return 0


_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTR_RE = re.compile(
    r"^\s*(ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(([^)]*)")
_TYPE_SHAPES_RE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")


def copy_ops(hlo: str, shapes: Iterable[str]) -> List[str]:
    """Copy ops whose result starts with one of ``shapes`` — a donated
    buffer that silently lost its donation shows up as exactly such a
    copy (the HLO sometimes carries a layout suffix, hence prefix
    matching).

    Every copy of a non-fusion computation counts. A copy inside a fusion
    body counts when its result can leave the fusion as a buffer: it is
    the body's ROOT or an operand of the ROOT tuple (a multi-output
    fusion), or a fusion calling the body outputs one of ``shapes`` (a
    dynamic-update-slice into a copied buffer, say). The copies left out
    are layout changes on read inside a fusion whose outputs are none of
    ``shapes`` — the CPU backend emits those when a consumer (LAPACK's
    column-major ``eigh``) wants another layout of a donated Gram it only
    reads."""
    shapes = tuple(shapes)

    def watched(t: str) -> bool:
        return any(t.startswith(s) for s in shapes)

    comps: Dict[str, list] = {}
    fusion_out: Dict[str, List[str]] = {}      # fused body -> output shapes
    body = None
    for line in hlo.splitlines():
        m = _COMP_RE.match(line)
        if m:
            body = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR_RE.match(line)
        if m is None or body is None:
            continue
        root, name, typ, op, args = m.groups()
        body.append((bool(root), name, typ, op, args))
        c = re.search(r"calls=%?([\w.\-]+)", line) if op == "fusion" \
            else None
        if c:
            fusion_out.setdefault(c.group(1), []).extend(
                _TYPE_SHAPES_RE.findall(typ))
    copies = []
    for cname, instrs in comps.items():
        fused = cname in fusion_out
        escapes = fused and any(watched(t) for t in fusion_out[cname])
        root_args = {a for is_root, _, _, op, args in instrs
                     if is_root and op == "tuple"
                     for a in re.findall(r"%?([\w.\-]+)", args)}
        copies += [typ.split("{")[0] for is_root, name, typ, op, _ in instrs
                   if op == "copy" and (not fused or is_root or escapes
                                        or name in root_args)]
    return [c for c in copies if watched(c)]


def convert_ops(hlo: str) -> List[Tuple[str, str]]:
    """(result_shape, operand_shape) for every dtype ``convert`` whose
    operand shape is inline in the instruction text. The dtype-flow pass
    matches these against the managed buffer/Gram shapes."""
    out = []
    for m in re.finditer(
            r"= ([a-z]+[0-9]+\[[0-9,]*\])[^=\n]*? convert\(([a-z]+[0-9]+"
            r"\[[0-9,]*\])", hlo):
        out.append((m.group(1), m.group(2)))
    return out


def collective_ops(hlo: str) -> List[Tuple[str, int]]:
    """(kind, operand_bytes) per collective instruction (``-done`` halves
    of async pairs are skipped so nothing double-counts)."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) (all-reduce|"
                     r"all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start|-done)?\(", line)
        if not m or m.group(3) == "-done":
            continue
        nbytes = 0
        for ms in _SHAPE_RE.finditer(m.group(1)):
            n = 1
            for d in ms.group(2).split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES.get(ms.group(1), 4)
        out.append((m.group(2), nbytes))
    return out


def allgather_shapes(hlo: str) -> List[str]:
    """Normalized result shape strings ("f32[4,26624]") of every
    all-gather instruction — the collective-budget pass matches these
    against the snapshot-buffer / Gram shape sets: a gather RESULTING in a
    buffer-shaped tensor is the reshard-to-replicated failure mode, even
    in programs (the fused step, the gated jump) whose model-parallel
    forward legitimately gathers activation-sized tensors."""
    out: List[str] = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) all-gather"
                     r"(?:-start)?\(", line)
        if not m:
            continue
        out.extend(f"{ms.group(1)}[{ms.group(2)}]"
                   for ms in _SHAPE_RE.finditer(m.group(1)))
    return out


def max_allgather_bytes(hlo: str) -> int:
    """Largest all-gather operand in an HLO text, in bytes — the audit
    primitive behind the "no buffer-sized all-gather" invariant
    (DESIGN.md §3.4/§7): the sharded Gram route psums O(n_sys·m²)
    partials and must never gather an O(m·n) buffer."""
    return max((b for k, b in collective_ops(hlo) if k == "all-gather"),
               default=0)


def parse_collectives(hlo: str) -> Tuple[Dict[str, int], Dict[str, int]]:
    """({kind: total_operand_bytes}, {kind: count}) — shard-local shapes;
    multiply by participating devices for global traffic. (The dry-run's
    §Roofline inventory and the collective-budget pass share this.)"""
    totals: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    for kind, nbytes in collective_ops(hlo):
        totals[kind] = totals.get(kind, 0) + nbytes
        counts[kind] = counts.get(kind, 0) + 1
    return totals, counts


def dmd_state_shapes(state) -> Tuple[Set[str], Set[str], Set[str]]:
    """(buffer_shapes, gram_shapes, all_dmd_shapes) of a TrainState — the
    shape strings the donation / dtype-flow / collective passes key on."""
    import jax

    bufs: Set[str] = set()
    grams: Set[str] = set()
    for kp, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if leaf is None:
            continue
        k = jax.tree_util.keystr(kp)
        if "dmd_buffers" in k:
            bufs.add(shape_str(leaf))
        elif "dmd_gram" in k:
            grams.add(shape_str(leaf))
    return bufs, grams, bufs | grams
