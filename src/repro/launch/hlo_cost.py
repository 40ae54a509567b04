"""Trip-count-aware cost analysis over optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body ONCE, so any
scanned computation (stacked layers, chunked attention, SSD chunk scans)
is under-reported by its trip count.  This walker parses the optimized
HLO, multiplies while bodies by their ``known_trip_count`` backend
config, and accumulates:

* ``flops``        -- dot MACs (2*result*K) + elementwise arithmetic,
* ``bytes``        -- an HBM traffic model: operand + result bytes of
                      every top-level op (fusion *boundaries*: internals
                      of a fusion don't touch HBM),
* ``coll_bytes``   -- collective operand bytes (all-gather/-reduce/
                      reduce-scatter/all-to-all/collective-permute), with
                      the same trip multipliers.

This is a structural model (no overlap, perfect DMA) -- exactly what a
roofline wants.
"""
from __future__ import annotations

import json
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["analyze_hlo", "compiled_cost_dict"]


def compiled_cost_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a dict (empty where the backend
    reports none)."""
    return compiled.cost_analysis() or {}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
    "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")
_OP_RE = re.compile(r"^((?:\([^()]*(?:\([^()]*\))?[^()]*\))|(?:[a-z0-9]+"
                    r"\[[0-9,]*\](?:\{[^}]*\})?))\s+([\w\-]+)\(")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[":{\s]+n["\s:]+["]?(\d+)')
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]*)\}")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "power", "maximum", "minimum",
    "exponential", "log", "tanh", "rsqrt", "sqrt", "negate", "abs",
    "floor", "ceil", "sign", "cosine", "sine", "logistic", "compare",
    "select", "and", "or", "xor", "not", "remainder", "atan2",
    "exponential-minus-one", "log-plus-one", "cbrt", "round-nearest-even",
    "erf", "shift-left", "shift-right-logical", "shift-right-arithmetic",
}
_ZERO_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
    # control flow: carries are donated in place; bodies are accounted
    # per-iteration separately
    "while", "conditional", "call", "optimization-barrier",
}
# ops that touch only their *result*-sized window of the operand, not the
# whole buffer (counting the full operand would charge a scan's stacked
# params once per iteration):
_WINDOW_READ_OPS = {"dynamic-slice", "slice", "gather", "broadcast",
                    "reshape"}
_WINDOW_WRITE_OPS = {"dynamic-update-slice", "scatter"}
_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")


def _shape_elems_bytes(dtype: str, dims: str) -> Tuple[int, int]:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n, n * _DTYPE_BYTES.get(dtype, 4)


def _result_bytes(result_txt: str) -> Tuple[int, int]:
    elems = nbytes = 0
    for d, s in _SHAPE_RE.findall(result_txt):
        e, b = _shape_elems_bytes(d, s)
        elems += e
        nbytes += b
    return elems, nbytes


class _Instr:
    __slots__ = ("name", "op", "result_txt", "elems", "nbytes", "operands",
                 "line")

    def __init__(self, name, op, result_txt, operands, line):
        self.name, self.op, self.result_txt = name, op, result_txt
        self.elems, self.nbytes = _result_bytes(result_txt)
        self.operands = operands
        self.line = line


def _split_computations(text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    buf: List[str] = []
    for line in text.splitlines():
        if not line.startswith(" ") and "{" in line and ("(" in line):
            m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)", line.strip())
            if m:
                cur = m.group(1)
                buf = []
                comps[cur] = buf
                if "ENTRY" in line:
                    comps["__entry__"] = buf
                continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            buf.append(line)
    return comps


def _parse_instr(line: str) -> Optional[_Instr]:
    m = _INSTR_RE.match(line)
    if not m:
        return None
    name, rhs = m.groups()
    m2 = _OP_RE.match(rhs)
    if not m2:
        return None
    result_txt, op = m2.groups()
    # operand names: first (...) group after op name
    start = rhs.find(op + "(") + len(op) + 1
    depth, i = 1, start
    while i < len(rhs) and depth:
        if rhs[i] == "(":
            depth += 1
        elif rhs[i] == ")":
            depth -= 1
        i += 1
    operand_txt = rhs[start:i - 1]
    operands = re.findall(r"%([\w\.\-]+)", operand_txt)
    return _Instr(name, op, result_txt, operands, line)


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(len([t for t in m.group(1).split(",") if t]), 1)
    return 1


def analyze_hlo(text: str) -> Dict[str, float]:
    comps = _split_computations(text)
    parsed: Dict[str, List[_Instr]] = {}
    symtab: Dict[str, Dict[str, _Instr]] = {}
    for cname, lines in comps.items():
        if cname == "__entry__":
            continue
        instrs = [i for i in (_parse_instr(l) for l in lines) if i]
        parsed[cname] = instrs
        symtab[cname] = {i.name: i for i in instrs}

    fusion_param_bytes: Dict[str, Dict[int, float]] = {}

    def _fusion_operand_bytes(cname: str) -> Dict[int, float]:
        """Effective HBM bytes read per fusion parameter: if a parameter is
        only consumed through window reads (dynamic-slice/gather/...), the
        fusion DMAs the windows, not the whole buffer."""
        if cname in fusion_param_bytes:
            return fusion_param_bytes[cname]
        out: Dict[int, float] = {}
        instrs = parsed.get(cname, [])
        params = {}
        for ins in instrs:
            if ins.op == "parameter":
                m = re.search(r"parameter\((\d+)\)", ins.line)
                if m:
                    params[ins.name] = (int(m.group(1)), ins.nbytes)
        for pname, (idx, full) in params.items():
            uses = [i for i in instrs if pname in i.operands]
            if uses and all(u.op in _WINDOW_READ_OPS for u in uses):
                out[idx] = float(sum(u.nbytes for u in uses))
            else:
                out[idx] = float(full)
        fusion_param_bytes[cname] = out
        return out

    memo: Dict[Tuple[str, bool], Tuple[float, float, float, Dict[str, float]]] = {}

    bytes_by_op: Dict[str, float] = {}

    def _acc_op(op: str, nbytes: float, mult: float = 1.0):
        bytes_by_op[op] = bytes_by_op.get(op, 0.0) + nbytes * mult

    def cost(cname: str, stream: bool):
        """Returns (flops, bytes, coll_bytes, coll_by_class)."""
        key = (cname, stream)
        if key in memo:
            return memo[key]
        memo[key] = (0.0, 0.0, 0.0, {})  # cycle guard
        fl = by = co = 0.0
        cls: Dict[str, float] = {}
        for ins in parsed.get(cname, []):
            op = ins.op
            if op == "dot":
                k = 1
                mC = _LHS_CONTRACT_RE.search(ins.line)
                if mC and ins.operands:
                    lhs = symtab[cname].get(ins.operands[0])
                    if lhs:
                        shapes = _SHAPE_RE.findall(lhs.result_txt)
                        if shapes:
                            dims = [int(d) for d in shapes[0][1].split(",")
                                    if d]
                            for ci in mC.group(1).split(","):
                                if ci and int(ci) < len(dims):
                                    k *= dims[int(ci)]
                fl += 2.0 * ins.elems * k
            elif op in _ELEMENTWISE:
                fl += ins.elems
            elif op == "fusion":
                mcall = _CALLS_RE.search(ins.line)
                if mcall:
                    f2, _, c2, cl2 = cost(mcall.group(1), False)
                    fl += f2
                    co += c2
                    for kk, vv in cl2.items():
                        cls[kk] = cls.get(kk, 0.0) + vv
                    if stream:
                        eff = _fusion_operand_bytes(mcall.group(1))
                        nb = ins.nbytes + sum(eff.values())
                        by += nb
                        _acc_op("fusion", nb)
                continue
            elif op == "while":
                mb = _BODY_RE.search(ins.line)
                mt = _TRIP_RE.search(ins.line)
                trip = int(mt.group(1)) if mt else 1
                if mb:
                    f2, b2, c2, cl2 = cost(mb.group(1), True)
                    fl += trip * f2
                    by += trip * b2
                    co += trip * c2
                    for kk, vv in cl2.items():
                        cls[kk] = cls.get(kk, 0.0) + trip * vv
            elif op == "conditional":
                mbr = _BRANCH_RE.search(ins.line)
                if mbr:
                    branches = re.findall(r"%?([\w\.\-]+)",
                                          mbr.group(1))
                    if branches:
                        sub = [cost(b, True) for b in branches]
                        best = max(sub, key=lambda t: t[0] + t[1])
                        fl += best[0]
                        by += best[1]
                        co += best[2]
                        for kk, vv in best[3].items():
                            cls[kk] = cls.get(kk, 0.0) + vv
            elif any(op.startswith(c) for c in _COLL_OPS):
                if op.endswith("-done"):
                    continue
                base = op.replace("-start", "")
                cbytes = ins.nbytes
                if base == "all-reduce" and op.endswith("-start"):
                    cbytes //= 2   # tuple result aliases (operand, result)
                if base == "all-gather":
                    cbytes //= _group_size(ins.line)
                elif base == "reduce-scatter":
                    cbytes *= _group_size(ins.line)
                co += cbytes
                cls[base] = cls.get(base, 0.0) + cbytes
            if stream and op not in _ZERO_BYTES_OPS:
                if op in _WINDOW_READ_OPS:
                    # reads only a result-sized window (+ tiny indices)
                    by += 2 * ins.nbytes
                    _acc_op(op, 2 * ins.nbytes)
                elif op in _WINDOW_WRITE_OPS:
                    # reads the update operand, writes a window of it
                    upd = (symtab[cname].get(ins.operands[1])
                           if len(ins.operands) > 1 else None)
                    ub = upd.nbytes if upd is not None else ins.nbytes
                    by += 2 * min(ub, ins.nbytes)
                    _acc_op(op, 2 * min(ub, ins.nbytes))
                else:
                    opb = 0
                    for oname in ins.operands:
                        o = symtab[cname].get(oname)
                        if o is not None:
                            opb += o.nbytes
                    by += ins.nbytes + opb
                    _acc_op(op, ins.nbytes + opb)
        memo[key] = (fl, by, co, cls)
        return memo[key]

    entry_name = None
    for cname in parsed:
        if ".main" in cname or cname.startswith("main"):
            entry_name = cname
    if entry_name is None and parsed:
        # fall back: the computation that no one calls
        called = set()
        for cname, instrs in parsed.items():
            for ins in instrs:
                for rx in (_CALLS_RE, _BODY_RE):
                    mm = rx.search(ins.line)
                    if mm:
                        called.add(mm.group(1))
        rest = [c for c in parsed if c not in called]
        entry_name = rest[-1] if rest else list(parsed)[-1]

    fl, by, co, cls = cost(entry_name, True)
    top = dict(sorted(bytes_by_op.items(), key=lambda kv: -kv[1])[:12])
    out = {"flops": fl, "bytes": by, "coll_bytes": co, "entry": entry_name,
           "bytes_by_op_unscaled": top}
    for k, v in cls.items():
        out[f"coll_{k}"] = v
    return out
