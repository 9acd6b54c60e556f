"""HLO-text cost model with correct loop accounting.

``compiled.cost_analysis()`` counts each ``while`` body ONCE — with
scan-over-layers and grad-accumulation scans, that undercounts FLOPs,
bytes and collective traffic by the product of trip counts (~40-1500x).
This module re-derives costs from ``compiled.as_text()``:

1. split the module into computations; per computation build a
   name -> result-shape map (optimized HLO references operands by NAME
   only, so dot contraction sizes must be resolved through the map),
2. build the call graph (fusion ``calls=``, while ``body=/condition=``,
   conditional ``branch_computations=``, ``to_apply=``),
3. recover each while loop's trip count from its condition computation
   (``compare(iter, constant(N)), direction=LT``),
4. propagate multipliers from ENTRY and sum per-computation costs:
     - dot FLOPs   = 2 * prod(result_shape) * contraction_size
     - convolution = 2 * prod(result_shape) * (kernel window * Cin / Cout)
     - HBM traffic = result + operand bytes at *materialization* level
       only: ops inside fusion/apply computations stay in registers/VMEM
       and are NOT counted; fusion ops are counted at their call site.
       In-place dynamic-update-slice (KV-cache append) is counted as the
       update-slice bytes, not the whole aliased buffer.
     - collectives = ring wire-bytes (same factors as roofline.py)

The counter is validated against closed-form 6ND in tests/test_roofline.py.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.launch.roofline import _DTYPE_BYTES, _group_size


_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_START = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?.*\{\s*$")
_CALL_ATTR = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OP_LINE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+)$")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONST_S32 = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_COMPARE_LT = re.compile(r"compare\(.*direction=LT")
_COLL_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# ops that move no bytes at the materialization level (views / bookkeeping /
# control flow whose bodies are costed separately)
_NO_TRAFFIC = {
    "parameter",
    "tuple",
    "get-tuple-element",
    "bitcast",
    "constant",
    "after-all",
    "add-dependency",
    "while",
    "conditional",
    "call",
    "opt-barrier",
    "partition-id",
    "replica-id",
}


def _prod(dims: List[int]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _nbytes(shapes: List[Tuple[str, List[int]]]) -> int:
    return sum(_prod(dims) * _DTYPE_BYTES.get(dt, 0) for dt, dims in shapes)


@dataclass
class Op:
    name: str
    kind: str
    shapes: List[Tuple[str, List[int]]]  # result shape(s)
    operands: List[str]  # operand names (no leading %)
    rhs: str  # full text after '='


@dataclass
class Comp:
    ops: List[Op] = field(default_factory=list)
    shape_of: Dict[str, List[Tuple[str, List[int]]]] = field(default_factory=dict)
    # call edges: (kind, callee); kind in
    #   while_body | while_cond | branch | fusion | apply | call
    calls: List[Tuple[str, str]] = field(default_factory=list)


def _split_result_and_op(rhs: str) -> Tuple[str, str, str]:
    """'f32[2,4]{1,0} dot(%a, %b), attrs' ->
    ('f32[2,4]{1,0} ', 'dot', '(%a, %b), attrs...').  Tuple results keep
    their balanced-paren region intact."""
    rhs = rhs.strip()
    i = 0
    if rhs.startswith("("):  # tuple result type
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    j = rhs.find("(", i)
    if j < 0:
        return rhs, "", ""
    # mnemonic = last word before the paren
    head = rhs[i:j].strip()
    kind = head.split()[-1] if head.split() else ""
    return rhs[:i] + head[: -len(kind)] if kind else rhs[:j], kind, rhs[j:]


def _arg_region(after_paren: str) -> str:
    """Balanced first paren group: '(%a, %b), attrs' -> '%a, %b'."""
    depth = 0
    for i, ch in enumerate(after_paren):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return after_paren[1:i]
    return after_paren[1:]


_NAME_RE = re.compile(r"%([\w\.\-]+)")


def parse_computations(hlo: str) -> Tuple[Dict[str, Comp], Optional[str]]:
    comps: Dict[str, Comp] = {}
    entry: Optional[str] = None
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if not line.strip():
            continue
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            m = _COMP_START.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = Comp()
                if line.startswith("ENTRY"):
                    entry = cur
            continue
        if line.strip() == "}" or cur is None:
            continue
        mo = _OP_LINE.match(line)
        if not mo:
            continue
        name, rhs = mo.group(1), mo.group(2)
        result_region, kind, rest = _split_result_and_op(rhs)
        shapes = [
            (m.group(1), [int(d) for d in m.group(2).split(",")] if m.group(2) else [])
            for m in _SHAPE_RE.finditer(result_region)
            if m.group(1) in _DTYPE_BYTES
        ]
        operands = _NAME_RE.findall(_arg_region(rest)) if rest else []
        comp = comps[cur]
        op = Op(name, kind, shapes, operands, rhs)
        comp.ops.append(op)
        comp.shape_of[name] = shapes
        # ---- call edges ------------------------------------------------
        if kind == "while":
            b = re.search(r"body=%?([\w\.\-]+)", rhs)
            c = re.search(r"condition=%?([\w\.\-]+)", rhs)
            if b:
                comp.calls.append(("while_body", b.group(1)))
            if c:
                comp.calls.append(("while_cond", c.group(1)))
        elif kind == "conditional":
            bm = _BRANCHES.search(rhs)
            if bm:
                for b in bm.group(1).split(","):
                    comp.calls.append(("branch", b.strip().lstrip("%")))
        elif kind == "fusion":
            for callee in _CALL_ATTR.findall(rhs):
                comp.calls.append(("fusion", callee))
        elif kind == "call":
            for callee in _CALL_ATTR.findall(rhs):
                comp.calls.append(("call", callee))
        else:  # reduce / sort / map / scatter / custom-call to_apply
            for callee in _CALL_ATTR.findall(rhs):
                comp.calls.append(("apply", callee))
    return comps, entry


def _trip_count(cond: Optional[Comp]) -> int:
    """Trip count from a while condition: the constant in compare(...,LT)."""
    if cond is None:
        return 1
    best = 1
    for op in cond.ops:
        if _COMPARE_LT.search(op.rhs):
            for c in _CONST_S32.findall(op.rhs):
                best = max(best, int(c))
    if best > 1:
        return best
    for op in cond.ops:  # constant may be on a separate line
        for c in _CONST_S32.findall(op.rhs):
            best = max(best, int(c))
    return best


def _dot_flops(op: Op, comp: Comp) -> float:
    out_elems = _prod(op.shapes[0][1]) if op.shapes else 0
    c = _CONTRACT.search(op.rhs)
    if not op.operands or not c:
        return 0.0
    lhs = comp.shape_of.get(op.operands[0])
    if not lhs:
        return 0.0
    lhs_dims = lhs[0][1]
    k = 1
    if c.group(1):
        for di in c.group(1).split(","):
            if int(di) < len(lhs_dims):
                k *= lhs_dims[int(di)]
    return 2.0 * out_elems * k


def _conv_flops(op: Op, comp: Comp) -> float:
    out_elems = _prod(op.shapes[0][1]) if op.shapes else 0
    if len(op.operands) < 2:
        return 0.0
    kshape = comp.shape_of.get(op.operands[1])
    if not kshape or not kshape[0][1]:
        return 0.0
    kdims = kshape[0][1]
    k = _prod(kdims)
    cout = kdims[-1] if kdims else 1  # HWIO kernel
    return 2.0 * out_elems * (k / max(cout, 1))


def _wire_bytes(op: Op) -> float:
    nbytes = _nbytes(op.shapes)
    g = _group_size(op.rhs)
    if g <= 1 and op.kind != "collective-permute":
        return 0.0
    frac = (g - 1) / g if g > 1 else 1.0
    if op.kind.startswith("all-gather"):
        return nbytes * frac
    if op.kind.startswith("reduce-scatter"):
        return nbytes * g * frac
    if op.kind.startswith("all-reduce"):
        return 2.0 * nbytes * frac
    if op.kind.startswith("all-to-all"):
        return nbytes * frac
    return float(nbytes)


def _has_inplace_dus(comp: Optional[Comp], result_bytes: int) -> bool:
    """Does this fused computation end in a dynamic-update-slice of the
    full result buffer (aliased in-place update, e.g. KV-cache append)?"""
    if comp is None:
        return False
    return any(
        op.kind == "dynamic-update-slice" and _nbytes(op.shapes) == result_bytes
        for op in comp.ops
    )


@dataclass
class ModuleCost:
    flops: float
    traffic_bytes: float
    wire_bytes: float
    wire_by_kind: Dict[str, float]
    coll_count: Dict[str, int]


def analyze_hlo(hlo: str) -> ModuleCost:
    comps, entry = parse_computations(hlo)
    if entry is None:
        return ModuleCost(0, 0, 0, {}, {})

    # memo keyed on (name, materializing): totals as
    # (flops, traffic, wire, wire_by_kind, coll_count)
    memo: Dict[Tuple[str, bool], Tuple[float, float, float, Dict[str, float], Dict[str, float]]] = {}

    def total(name: str, mat: bool, stack=()) -> Tuple[float, float, float, Dict[str, float], Dict[str, float]]:
        key = (name, mat)
        if key in memo:
            return memo[key]
        comp = comps.get(name)
        if comp is None or name in stack:
            return (0.0, 0.0, 0.0, {}, {})
        f = t = w = 0.0
        wk: Dict[str, float] = {}
        cc: Dict[str, float] = {}
        for op in comp.ops:
            if op.kind == "dot":
                f += _dot_flops(op, comp)
            elif op.kind == "convolution":
                f += _conv_flops(op, comp)
            if any(op.kind.startswith(k) for k in _COLL_KINDS) and not op.kind.endswith("-done"):
                wb = _wire_bytes(op)
                base = next(k for k in _COLL_KINDS if op.kind.startswith(k))
                w += wb
                wk[base] = wk.get(base, 0.0) + wb
                cc[base] = cc.get(base, 0.0) + 1
            if mat and op.kind not in _NO_TRAFFIC and op.kind:
                result_b = _nbytes(op.shapes)
                operand_b = sum(_nbytes(comp.shape_of.get(o, [])) for o in op.operands)
                if op.kind == "dynamic-update-slice" and op.operands:
                    big = _nbytes(comp.shape_of.get(op.operands[0], []))
                    t += result_b + operand_b - 2 * big
                elif op.kind == "fusion":
                    # find this op's own callee for the DUS-alias check
                    m = re.search(r"calls=%?([\w\.\-]+)", op.rhs)
                    callee = comps.get(m.group(1)) if m else None
                    if _has_inplace_dus(callee, result_b):
                        # aliased buffer appears as result AND operand;
                        # real traffic is just the update slice + indices
                        t += max(result_b + operand_b - 2 * max(
                            (_nbytes(comp.shape_of.get(o, [])) for o in op.operands),
                            default=0,
                        ), 0)
                    else:
                        t += result_b + operand_b
                else:
                    t += result_b + operand_b
        # recurse over call edges, grouping while body/cond pairs per op
        for op in comp.ops:
            if op.kind == "while":
                b = re.search(r"body=%?([\w\.\-]+)", op.rhs)
                c = re.search(r"condition=%?([\w\.\-]+)", op.rhs)
                trips = _trip_count(comps.get(c.group(1))) if c else 1
                for callee, mult in ((b, trips), (c, trips + 1)):
                    if callee is None:
                        continue
                    bf, bt, bw, bwk, bcc = total(callee.group(1), mat, stack + (name,))
                    f += bf * mult
                    t += bt * mult
                    w += bw * mult
                    for k, v in bwk.items():
                        wk[k] = wk.get(k, 0.0) + v * mult
                    for k, v in bcc.items():
                        cc[k] = cc.get(k, 0.0) + v * mult
            else:
                for kind, callee in _op_call_edges(op):
                    child_mat = mat and kind in ("branch", "call")
                    cf, ct, cw, cwk, ccc = total(callee, child_mat, stack + (name,))
                    f, t, w = f + cf, t + ct, w + cw
                    for k, v in cwk.items():
                        wk[k] = wk.get(k, 0.0) + v
                    for k, v in ccc.items():
                        cc[k] = cc.get(k, 0.0) + v
        memo[key] = (f, t, w, wk, cc)
        return memo[key]

    f, t, w, wk, cc = total(entry, True)
    return ModuleCost(f, t, w, wk, {k: int(v) for k, v in cc.items()})


def _op_call_edges(op: Op) -> List[Tuple[str, str]]:
    """Call edges contributed by ONE op line (kind, callee)."""
    if op.kind == "conditional":
        bm = _BRANCHES.search(op.rhs)
        if bm:
            return [("branch", b.strip().lstrip("%")) for b in bm.group(1).split(",")]
        return []
    kind_map = {"fusion": "fusion", "call": "call"}
    edge_kind = kind_map.get(op.kind, "apply")
    return [(edge_kind, c) for c in _CALL_ATTR.findall(op.rhs)]


_CONVERT_F32 = re.compile(r"%([\w\.\-]+) = f32\[([\d,]+)\][^=]*? convert\(%([\w\.\-]+)\)")


def cpu_bf16_upcast_bytes(hlo: str) -> float:
    """Total bytes of f32 tensors produced by convert(bf16) ops.

    XLA:CPU lowers bf16 dots/convs by upcasting operands to f32; these
    buffers do not exist on TPU (native bf16 MXU).  Deduped by result name;
    used to project the CPU dry-run's peak memory onto the TPU target:
    on TPU the converted copy is not materialized at all, so the projection
    subtracts the full f32 size (conservative: transient bf16 reads remain).
    """
    bf16_names = set(re.findall(r"%([\w\.\-]+) = bf16\[", hlo))
    seen = set()
    total = 0.0
    for m in _CONVERT_F32.finditer(hlo):
        name, dims, src = m.group(1), m.group(2), m.group(3)
        if name in seen or src not in bf16_names:
            continue
        seen.add(name)
        n = 1
        for d in dims.split(","):
            n *= int(d)
        total += 4.0 * n
    return total
