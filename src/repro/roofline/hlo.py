"""Optimized-HLO text analysis: FLOPs, HBM bytes, collective bytes.

``compiled.cost_analysis()`` has two blind spots the roofline cannot
live with: (1) it counts every ``while`` body ONCE — a scanned layer
stack under-reports FLOPs by ~num_layers x; (2) it reports no collective
traffic at all. This module rebuilds whole-program costs from
``compiled.as_text()``:

  * call-graph weights: ENTRY has weight 1; a while body inherits
    weight x trip_count (trip count recovered from the loop-condition
    computation's comparison constant); fusion bodies inherit their
    caller's weight;
  * FLOPs: every ``dot`` line contributes 2 x result_elems x
    contraction_size (operand shapes resolved through a per-computation
    symbol table — scheduled HLO prints operands as bare refs);
    ``convolution`` approximated as 2 x result x kernel_size;
  * HBM bytes: per-instruction I/O (result + resolved operands) at
    computation level, fusion bodies excluded (their internals live in
    registers/VMEM; the fusion instruction's own I/O is what moves);
  * collectives: ``all-gather``/``all-reduce``/``reduce-scatter``/
    ``all-to-all``/``collective-permute`` result bytes scaled by the
    ring-model wire cost, split ICI vs DCN by whether the replica group
    crosses a 256-chip pod boundary;
  * exchange bytes (``exchange_bytes``, the train step's
    ``train.exchange_bytes``): the same collectives' result bytes, each
    computation counted once per call site and each while body once per
    trip;
  * pool-move bytes (``pool_move_bytes``, the paged decode step's
    ``serve.pool_move_bytes``): bytes of the copies, slices and updates
    that hold one layer's KV pool slab or more, weighted the same way.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_OP_RE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_DOT_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_WHILE_RE = re.compile(r"condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w.\-]+)")

_BYTE_SKIP_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "reshape",
    "while", "conditional", "call",
}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _first_shape_dims(text: str) -> Tuple[int, ...]:
    m = _SHAPE_RE.search(text)
    if not m:
        return ()
    return tuple(int(d) for d in m.group(2).split(",") if d.strip())


def _strip_meta(line: str) -> str:
    return line.split(", metadata=")[0]


def _line_op(line: str) -> str:
    rhs = line.split("=", 1)[1] if "=" in line else line
    m = _OP_RE.search(_strip_meta(rhs))
    return m.group(1) if m else ""


def _result_text(line: str) -> str:
    """Text between '=' and the op name (the result shape)."""
    rhs = line.split("=", 1)[1] if "=" in line else line
    m = _OP_RE.search(_strip_meta(rhs))
    return rhs[:m.start()] if m else rhs


def _operand_names(line: str) -> List[str]:
    """Operand refs inside op(...) — before any attribute list."""
    rhs = _strip_meta(line.split("=", 1)[1] if "=" in line else line)
    m = _OP_RE.search(rhs)
    if not m:
        return []
    args = rhs[m.end():]
    # cut at the matching close paren (flat scan; nested parens rare in
    # operand lists of scheduled HLO)
    depth = 1
    for i, ch in enumerate(args):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                args = args[:i]
                break
    return re.findall(r"%([\w.\-]+)", args)


# --------------------------------------------------------------------------
# computations, symbol tables, call-graph weights
# --------------------------------------------------------------------------


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if line.startswith("HloModule"):
            continue
        if cur is None:
            if line.rstrip().endswith("{") and "(" in line:
                m = _HEADER_RE.match(line)
                if m:
                    cur = m.group(1)
                    comps[cur] = []
        else:
            if line.startswith("}") or line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line.rstrip())
    return comps


def _symbol_table(lines: List[str]) -> Dict[str, str]:
    """instr name -> result-shape text."""
    table: Dict[str, str] = {}
    for line in lines:
        m = _NAME_RE.match(line)
        if m:
            table[m.group(1)] = _result_text(line)
    return table


def _entry_name(hlo: str) -> Optional[str]:
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY\s+%?([\w.\-]+)", line)
            if m:
                return m.group(1)
    return None


def _trip_count(comps: Dict[str, List[str]], cond: str) -> int:
    """A scan's trip count: the largest constant its loop condition
    compares the counter with (loops count up from 0)."""
    consts = [int(x) for line in comps.get(cond, [])
              for x in _CONST_RE.findall(line)]
    return max(consts + [1])


def _call_weights(hlo: str, comps: Dict[str, List[str]]
                  ) -> Tuple[Dict[str, float], Dict[str, bool]]:
    """computation -> execution weight; computation -> is_fusion_body.

    A computation's weight is the sum over its call sites of the
    caller's weight, times the trip count where the site is a ``while``
    body. Call sites are ``calls=`` (fusions, ``async-start``), a
    ``call``'s ``to_apply=`` and a ``while``'s ``body=``; the
    ``async-update``/``async-done`` halves name their ``async-start``'s
    computation again and are not counted."""
    callers: Dict[str, List[Tuple[str, float]]] = {}
    fusion_body: Dict[str, bool] = {}

    for name, lines in comps.items():
        for line in lines:
            op = _line_op(line)
            wm = _WHILE_RE.search(line)
            if op == "while" and wm:
                callers.setdefault(wm.group(2), []).append(
                    (name, float(_trip_count(comps, wm.group(1)))))
                continue
            if op in ("async-update", "async-done"):
                continue
            callees = _CALLS_RE.findall(line)
            if op == "call":
                callees += _TO_APPLY_RE.findall(line)
            for callee in callees:
                callers.setdefault(callee, []).append((name, 1.0))
                if op == "fusion":
                    fusion_body[callee] = True

    entry = _entry_name(hlo) or (list(comps)[-1] if comps else None)
    weights: Dict[str, float] = {}

    def weight(name: str) -> float:
        if name == entry:
            return 1.0
        if name not in weights:
            weights[name] = 0.0           # a cycle adds nothing
            weights[name] = sum(weight(c) * mult
                                for c, mult in callers.get(name, []))
        return weights[name]

    return {c: weight(c) for c in comps}, fusion_body


# --------------------------------------------------------------------------
# program costs
# --------------------------------------------------------------------------


def _dot_flops(line: str, table: Dict[str, str]) -> int:
    res_elems = 1
    for d in _first_shape_dims(_result_text(line)):
        res_elems *= d
    ops = _operand_names(line)
    contract = 1
    if ops:
        lhs_dims = _first_shape_dims(table.get(ops[0], ""))
        m = _DOT_CONTRACT_RE.search(line)
        if m and lhs_dims:
            for idx in m.group(1).split(","):
                if idx.strip():
                    i = int(idx)
                    if i < len(lhs_dims):
                        contract *= lhs_dims[i]
    return 2 * res_elems * contract


def _conv_flops(line: str) -> int:
    res_elems = 1
    for d in _first_shape_dims(_result_text(line)):
        res_elems *= d
    m = re.search(r"window=\{size=([0-9x]+)", line)
    k = 1
    if m:
        for d in m.group(1).split("x"):
            k *= int(d)
    return 2 * res_elems * k


@dataclasses.dataclass
class ProgramCosts:
    flops: float                   # per-device, trip-weighted
    hbm_bytes: float               # per-device, trip-weighted (estimate)
    dot_count: int


_SLICE_LIKE = ("dynamic-slice", "gather", "slice")


def _instr_bytes(line: str, op: str, name: str,
                 table: Dict[str, str]) -> int:
    """HBM traffic of one instruction.

    Slice-like ops read only the addressed window, not their (possibly
    loop-invariant, stacked) operand — charging the full operand per
    trip would overstate a layer scan's traffic by ~L x. Rules:
      * dynamic-slice / gather / slice: 2 x result (read window + write)
      * dynamic-update-slice / scatter (incl. fused): 2 x the non-
        buffer operands (the buffer operand is result-shaped and only
        its window is touched)
      * everything else: result + resolved operand bytes.
    """
    res = _shape_bytes(_result_text(line))
    lowered_name = name.lower()
    if op in _SLICE_LIKE or any(s in lowered_name for s in _SLICE_LIKE):
        return 2 * res
    if (op in ("dynamic-update-slice", "scatter")
            or "dynamic-update-slice" in lowered_name
            or "scatter" in lowered_name):
        other = 0
        for o in _operand_names(line):
            b = _shape_bytes(table.get(o, ""))
            if b != res:                      # skip the buffer operand
                other += b
        return 2 * other if other else 2 * res
    io = res
    for o in _operand_names(line):
        io += _shape_bytes(table.get(o, ""))
    return io


def program_costs(hlo: str) -> ProgramCosts:
    comps = _split_computations(hlo)
    weights, fusion_body = _call_weights(hlo, comps)
    flops = 0.0
    bytes_ = 0.0
    dots = 0
    for name, lines in comps.items():
        w = weights.get(name, 0.0)
        if w <= 0:
            continue
        table = _symbol_table(lines)
        in_fusion = fusion_body.get(name, False)
        for line in lines:
            op = _line_op(line)
            if op == "dot":
                flops += w * _dot_flops(line, table)
                dots += 1
            elif op == "convolution":
                flops += w * _conv_flops(line)
            if not in_fusion and op and op not in _BYTE_SKIP_OPS:
                m = _NAME_RE.match(line)
                iname = m.group(1) if m else ""
                bytes_ += w * _instr_bytes(line, op, iname, table)
    return ProgramCosts(flops=flops, hbm_bytes=bytes_, dot_count=dots)


# --------------------------------------------------------------------------
# collectives
# --------------------------------------------------------------------------


def _group_info(line: str, pod_size: int = 256) -> Tuple[int, int]:
    """(group size, pods spanned) from the replica_groups annotation.

    Iota groups ``[G,P]<=[dims]T(perm)`` are materialized (device counts
    here are <= 512) so transposed layouts — e.g. the cross-pod pairs
    ``[256,2]<=[2,256]T(1,0)`` — classify correctly.
    """
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        ngroups, per_group = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        total = 1
        for d in dims:
            total *= d
        if total <= 65536:
            import numpy as _np
            ids = _np.arange(total).reshape(dims)
            if m.group(4):
                perm = [int(x) for x in m.group(4).split(",")]
                ids = ids.transpose(perm)
            first = ids.reshape(ngroups, per_group)[0]
            pods = len({int(i) // pod_size for i in first})
            return per_group, max(pods, 1)
        return per_group, 2 if per_group > pod_size else 1
    m = _GROUPS_RE.search(line)
    if m:
        ids = [int(x) for x in m.group(1).split(",") if x.strip()]
        pods = {i // pod_size for i in ids}
        return max(len(ids), 1), max(len(pods), 1)
    return 1, 1


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_type: Dict[str, int]
    ici_bytes: int                  # per-device wire bytes, intra-pod
    dcn_bytes: int                  # per-device wire bytes, cross-pod
    count: int

    @property
    def total_bytes(self) -> int:
        return self.ici_bytes + self.dcn_bytes


def collective_stats(hlo: str, pod_size: int = 256) -> CollectiveStats:
    comps = _split_computations(hlo)
    weights, _ = _call_weights(hlo, comps)

    by_type: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    ici = 0
    dcn = 0
    count = 0

    for name, lines in comps.items():
        w = weights.get(name, 0.0)
        if w <= 0:
            continue
        for line in lines:
            op = _line_op(line)
            base = op.replace("-start", "")
            if op.endswith("-done") or base not in _COLLECTIVES:
                continue
            size = _shape_bytes(_result_text(line))
            n, pods = _group_info(line, pod_size)
            if base == "all-reduce":
                wire = 2 * size * (n - 1) // max(n, 1)
            elif base == "collective-permute":
                wire = size
            else:
                wire = size * (n - 1) // max(n, 1)
            wire = int(wire * w)
            by_type[base] += wire
            # pod-crossing groups decompose hierarchically (XLA and any
            # sane runtime): the cross-pod leg moves (pods-1)/pods of
            # the payload over DCN, the rest stays on ICI
            if pods > 1:
                dcn_part = int(size * (pods - 1) // pods * w)
                dcn += min(dcn_part, wire)
                ici += max(wire - dcn_part, 0)
            else:
                ici += wire
            count += int(w)
    return CollectiveStats(bytes_by_type=by_type, ici_bytes=ici,
                           dcn_bytes=dcn, count=count)


# --------------------------------------------------------------------------
# exchange bytes: what the train step's collectives put out, per device
# --------------------------------------------------------------------------

# a collective's own opcode, or the ``-done`` half of an asynchronous one
# (its ``-start`` half is not counted: its result carries the operand too)
_EXCHANGE_OPS = frozenset(_COLLECTIVES) | frozenset(
    f"{c}-done" for c in ("all-reduce", "all-gather", "collective-permute"))


def exchange_bytes(hlo: str) -> int:
    """Bytes the collectives of a compiled module put out on one device
    in one execution: the result bytes of every ``all-gather``,
    ``all-reduce``, ``reduce-scatter``, ``all-to-all`` and
    ``collective-permute``, wherever it sits (fusions and called
    computations included), weighted as :func:`_call_weights` weighs
    its computation. A module on one device has none and reads 0."""
    comps = _split_computations(hlo)
    weights, _ = _call_weights(hlo, comps)
    return int(sum(weights.get(name, 0.0) * _shape_bytes(_result_text(line))
                   for name, lines in comps.items() for line in lines
                   if _line_op(line) in _EXCHANGE_OPS))


# --------------------------------------------------------------------------
# pool-move bytes: whole-slab data movement in the paged decode step
# --------------------------------------------------------------------------

_MOVE_RESULT_OPS = ("copy", "dynamic-slice")
_MOVE_UPDATE_OPS = ("dynamic-update-slice", "scatter")


def _update_operands(op: str, operands: List[str]) -> List[str]:
    """The operands an in-place update writes: a dynamic-update-slice's
    update (its second operand), a scatter's updates (the last N of its
    N buffers, one index array, N updates)."""
    if op == "dynamic-update-slice":
        return operands[1:2]
    return operands[(len(operands) - 1) // 2 + 1:]


def pool_move_bytes(hlo: str, slabs: Sequence[Tuple[int, ...]]) -> int:
    """Bytes a compiled module moves in one execution in ops that hold at
    least one layer's pool slab: a shape ending in one of ``slabs`` (a
    layer's (blocks, block size, ...) of each pool leaf, on one device),
    as a layer's slab, a stack of layers or the whole pool has. Moved
    are the result of a ``copy`` or ``dynamic-slice`` and the update
    operands of a ``dynamic-update-slice`` or ``scatter``, wherever they
    sit (fusions included), each weighted as :func:`_call_weights` weighs
    its computation. Shapes, not sizes, pick the ops: a model's per-layer
    weight slices may be as large as a slab. A decode step that carries
    its pool through the layer scan and writes each token in place reads
    0."""
    slabs = [tuple(sl) for sl in slabs]

    def holds_a_slab(text: str) -> bool:
        dims = _first_shape_dims(text)
        return any(dims[len(dims) - len(sl):] == sl for sl in slabs
                   if len(dims) >= len(sl))

    comps = _split_computations(hlo)
    weights, _ = _call_weights(hlo, comps)
    total = 0.0
    for name, lines in comps.items():
        w = weights.get(name, 0.0)
        if w <= 0:
            continue
        table = None
        for line in lines:
            op = _line_op(line)
            if op in _MOVE_RESULT_OPS:
                moved = [_result_text(line)]
            elif op in _MOVE_UPDATE_OPS:
                table = table or _symbol_table(lines)
                moved = [table.get(o, "") for o in
                         _update_operands(op, _operand_names(line))]
            else:
                continue
            total += w * sum(_shape_bytes(t) for t in moved
                             if holds_a_slab(t))
    return int(total)
