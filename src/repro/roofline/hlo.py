"""Trip-count-aware cost extraction from optimized (SPMD-partitioned) HLO.

``compiled.cost_analysis()`` counts ``while`` bodies ONCE, which silently
drops a factor of num_layers from every scanned-layer model.  XLA's
optimized HLO carries ``backend_config={"known_trip_count":{"n":...}}`` on
each while, so we parse the module into computations, propagate loop
multipliers through while-body/fusion/call edges, and accumulate:

* **flops** — every ``dot`` (2 * prod(result) * prod(contracting dims)) and
  ``convolution`` (2 * prod(result) * kernel work per output element);
* **bytes** — result + operand bytes of ops in *non-fusion* computations
  (fusion internals live in registers/VMEM, so only fusion boundaries touch
  HBM — this matches the XLA execution model);
* **collectives** — wire bytes per op kind, ring-scaled, x loop multiplier.

``op_scopes`` reads the program's named scopes back from the same text
(each instruction's ``metadata={op_name=".../<scope>/<op>"}``),
``fused_work_scopes`` the scopes whose work each fusion computes, and
``scope_flops`` counts the flops of each scope.

Validated against an unrolled single-device lowering in
tests/test_roofline.py (scan vs unroll agree).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_COMP_HDR = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((?:[^()]|\([^()]*\))*\)\s*->")
# a tuple's element layouts may hold one level of parentheses (TPU tiling:
# ``{1,0:T(8,128)(2,1)}``)
_OP_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\((?:[^()]|\([^()]*\))*\)|[\w]+\[[\d,]*\]"
    r"(?:\{[^}]*\})?)\s*([\w\-]+)\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_BODY = re.compile(r"body=%?([\w.\-]+)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"to_apply=%?([\w.\-]+)")
_COND = re.compile(r"condition=%?([\w.\-]+)")
_LHS_C = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_RG = re.compile(r"replica_groups=\{\{([^}]*)\}")
_RG2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_OP_NAME = re.compile(r'metadata=\{[^}]*\bop_name="([^"]*)"')

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "while", "call",
    "conditional", "custom-call", "infeed", "outfeed", "rng-get-and-update-state",
}


def _shape_list(type_str: str) -> List[Tuple[str, List[int]]]:
    out = []
    for dt, dims in _SHAPE.findall(type_str):
        out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _nbytes(shapes: List[Tuple[str, List[int]]]) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    shapes: List[Tuple[str, List[int]]]
    line: str


@dataclasses.dataclass
class ModuleCosts:
    flops: float
    hbm_bytes: float
    collective_wire_bytes: float
    collective_counts: Dict[str, int]
    loop_multipliers: Dict[str, float]


def parse_computations(text: str) -> Dict[str, List[Op]]:
    comps: Dict[str, List[Op]] = {}
    cur: Optional[str] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and ("{" in line) and ("->" in line):
            m = _COMP_HDR.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if line.strip() == "}":
            continue
        if cur is None:
            continue
        m = _OP_LINE.match(line)
        if m:
            name, type_str, opcode = m.groups()
            comps[cur].append(Op(name, opcode, _shape_list(type_str), line))
    return comps


def _entry_name(text: str) -> Optional[str]:
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR.match(line[len("ENTRY"):].strip() if False else
                                line.replace("ENTRY", "", 1).strip())
            if m:
                return m.group(1)
    return None


def loop_multipliers(text: str, comps: Dict[str, List[Op]]) -> Dict[str, float]:
    entry = _entry_name(text)
    mult: Dict[str, float] = {}
    if entry is None:
        return {c: 1.0 for c in comps}
    mult[entry] = 1.0
    # iterate to fixpoint (call graph is a DAG; few passes suffice)
    for _ in range(32):
        changed = False
        for cname, ops in comps.items():
            m0 = mult.get(cname)
            if m0 is None:
                continue
            for op in ops:
                targets: List[Tuple[str, float]] = []
                if op.opcode == "while":
                    trip = 1.0
                    tm = _TRIP.search(op.line)
                    if tm:
                        trip = float(tm.group(1))
                    bm = _BODY.search(op.line)
                    cm = _COND.search(op.line)
                    if bm:
                        targets.append((bm.group(1), m0 * trip))
                    if cm:
                        targets.append((cm.group(1), m0 * (trip + 1)))
                else:
                    for rex in (_CALLS, _TO_APPLY):
                        mm = rex.search(op.line)
                        if mm:
                            targets.append((mm.group(1), m0))
                for tgt, val in targets:
                    if tgt in comps and mult.get(tgt, 0.0) < val:
                        mult[tgt] = val
                        changed = True
        if not changed:
            break
    for c in comps:
        mult.setdefault(c, 1.0)
    return mult


def _symbol_table(comps: Dict[str, List[Op]]) -> Dict[str, List[Tuple[str, List[int]]]]:
    table: Dict[str, List[Tuple[str, List[int]]]] = {}
    for ops in comps.values():
        for op in ops:
            table[op.name] = op.shapes
    return table


def _operands(line: str) -> List[str]:
    """Names of the operands: what follows the opcode, up to its ``)``."""
    m = _OP_LINE.match(line)
    if not m:
        return []
    return re.findall(r"%([\w.\-]+)", line[m.end():].split(")", 1)[0])


def _dot_flops(op: Op, table) -> float:
    res = 1
    for _, dims in op.shapes:
        for d in dims:
            res *= d
    lhs_c = _LHS_C.search(op.line)
    contracted = 1
    if lhs_c:
        operands = _operands(op.line)
        if operands:
            lhs_shapes = table.get(operands[0])
            if lhs_shapes:
                dims = lhs_shapes[0][1]
                for idx in (int(i) for i in lhs_c.group(1).split(",") if i):
                    if idx < len(dims):
                        contracted *= dims[idx]
    return 2.0 * res * contracted


def _conv_flops(op: Op, table) -> float:
    res = 1
    for _, dims in op.shapes:
        for d in dims:
            res *= d
    operands = _operands(op.line)
    kernel_work = 1
    if len(operands) >= 2:
        ker = table.get(operands[1])
        if ker:
            dims = ker[0][1]
            total = 1
            for d in dims:
                total *= d
            # per-output-element work = prod(kernel)/out_features; the
            # out-features dim is the one matching the result feature count —
            # approximate with the largest trailing dim
            out_feat = dims[-1] if dims else 1
            kernel_work = max(1, total // max(1, out_feat))
    return 2.0 * res * kernel_work


def _param_read_bytes(comps: Dict[str, List[Op]]) -> Dict[str, List[Optional[int]]]:
    """Per fusion computation: effective read bytes per parameter position.

    A parameter consumed ONLY via dynamic-slice reads just the slice (the
    scan residual-stash pattern); anything else reads the full buffer
    (None = full).  This is what keeps the HBM-traffic proxy honest for
    scanned-layer models.
    """
    out: Dict[str, List[Optional[int]]] = {}
    for cname, ops in comps.items():
        params: Dict[str, int] = {}
        for op in ops:
            if op.opcode == "parameter":
                m = re.search(r"parameter\((\d+)\)", op.line)
                if m:
                    params[op.name] = int(m.group(1))
        if not params:
            continue
        # consumer map: param -> (all_dynamic_slice, slice_bytes); layout ops
        # (bitcast/reshape/transpose/copy) alias transitively to the param
        layout_ops = {"bitcast", "reshape", "transpose", "copy"}
        alias: Dict[str, str] = {p: p for p in params}
        info: Dict[str, Tuple[bool, int]] = {p: (True, 0) for p in params}
        for op in ops:
            if op.opcode == "parameter":
                continue
            operands = _operands(op.line)
            if (op.opcode in layout_ops and len(operands) == 1
                    and operands[0] in alias):
                alias[op.name] = alias[operands[0]]
                continue
            for i, o in enumerate(operands):
                root = alias.get(o)
                if root is None:
                    continue
                ok, nb = info[root]
                if op.opcode == "dynamic-slice" and i == 0:
                    info[root] = (ok, nb + _nbytes(op.shapes))
                elif op.opcode == "dynamic-update-slice" and i == 0:
                    # in-place update target: written slice counted via the
                    # update operand; the buffer itself is not fully read
                    continue
                else:
                    info[root] = (False, nb)
        n = max(params.values()) + 1
        eff: List[Optional[int]] = [None] * n
        for p, idx in params.items():
            ok, nb = info[p]
            if ok and nb >= 0:
                eff[idx] = nb
        out[cname] = eff
    return out


def _root_dus_write_bytes(comps, table) -> Dict[str, int]:
    """Fusions whose ROOT is a dynamic-update-slice write only the update
    slice in place, not the full (possibly stacked) buffer."""
    out: Dict[str, int] = {}
    for cname, ops in comps.items():
        for op in ops:
            if "ROOT" not in op.line or op.opcode != "dynamic-update-slice":
                continue
            operands = _operands(op.line)
            if len(operands) >= 2:
                upd = table.get(operands[1])
                if upd:
                    out[cname] = _nbytes(upd)
    return out


def _group_size(line: str, default: int) -> int:
    m = _RG.search(line)
    if m:
        return max(2, len(m.group(1).split(",")))
    m2 = _RG2.search(line)
    if m2:
        return max(2, int(m2.group(2)))
    return max(2, default)


def module_costs(text: str, num_devices: int) -> ModuleCosts:
    comps = parse_computations(text)
    mult = loop_multipliers(text, comps)
    table = _symbol_table(comps)
    param_reads = _param_read_bytes(comps)
    dus_roots = _root_dus_write_bytes(comps, table)
    flops = 0.0
    hbm = 0.0
    coll_bytes = 0.0
    coll_counts: Dict[str, int] = {}
    fusion_like = {c for c in comps
                   if c.startswith(("fused_", "wrapped_", "region_", "wide."))
                   or ".fused" in c or "_computation" in c
                   or ".clone" in c or "region_" in c}
    # computations reachable only as while bodies are NOT fusion-internal
    body_comps = set()
    for ops in comps.values():
        for op in ops:
            if op.opcode == "while":
                bm = _BODY.search(op.line)
                if bm:
                    body_comps.add(bm.group(1))
    for cname, ops in comps.items():
        m = mult.get(cname, 1.0)
        count_bytes_here = (cname in body_comps) or (cname not in fusion_like)
        for op in ops:
            if op.opcode == "dot":
                flops += m * _dot_flops(op, table)
            elif op.opcode == "convolution":
                flops += m * _conv_flops(op, table)
            base = op.opcode.replace("-start", "").replace("-done", "")
            if base in COLLECTIVES and not op.opcode.endswith("-done"):
                g = _group_size(op.line, num_devices)
                ring = (g - 1) / g
                factor = {"all-gather": ring, "reduce-scatter": ring,
                          "all-reduce": 2 * ring, "all-to-all": ring,
                          "collective-permute": 1.0}[base]
                coll_bytes += m * _nbytes(op.shapes) * factor
                coll_counts[base] = coll_counts.get(base, 0) + int(m)
            if not count_bytes_here or op.opcode in _SKIP_BYTES_OPS:
                continue
            # HBM traffic: results written + operands read at fusion
            # boundaries (fusion internals stay on-chip; dynamic-slice-only
            # fusion params read just their slices)
            operands = _operands(op.line)
            if op.opcode == "dynamic-update-slice" and len(operands) >= 2:
                upd = table.get(operands[1])
                hbm += m * 2 * (_nbytes(upd) if upd else 0)
                continue
            if op.opcode == "dynamic-slice":
                hbm += m * 2 * _nbytes(op.shapes)
                continue
            nb = _nbytes(op.shapes)
            callee = None
            if op.opcode == "fusion":
                cm = _CALLS.search(op.line)
                if cm:
                    callee = param_reads.get(cm.group(1))
                    if cm.group(1) in dus_roots:
                        nb = dus_roots[cm.group(1)]  # in-place slice write
            for i, o in enumerate(operands):
                sh = table.get(o)
                if sh is None:
                    continue
                full = _nbytes(sh)
                if callee is not None and i < len(callee) and callee[i] is not None:
                    nb += min(full, callee[i])
                else:
                    nb += full
            hbm += m * nb
    return ModuleCosts(flops=flops, hbm_bytes=hbm,
                       collective_wire_bytes=coll_bytes,
                       collective_counts=coll_counts,
                       loop_multipliers={k: v for k, v in mult.items()
                                         if v > 1.0})


def _scope_of(op_name: str, names) -> Optional[str]:
    """The innermost of ``names`` whose ``/``-separated parts appear in
    ``op_name`` as consecutive parts."""
    parts = op_name.split("/")
    best, best_at = None, -1
    for name in names:
        want = name.split("/")
        n = len(want)
        for i in range(len(parts) - n, -1, -1):
            if parts[i:i + n] == want:
                if i > best_at:
                    best, best_at = name, i
                break
    return best


def op_scopes(text: str, names) -> Dict[str, str]:
    """{instruction name: scope} for every instruction of the module whose
    metadata names one of ``names`` (``jax.named_scope`` names such as
    ``dit/attention``).  A fusion whose own metadata names none takes the
    scope most of its fused computation's instructions carry.  An
    instruction with no metadata at all (one a compiler pass made, such as
    a dot with its batch dimensions moved) takes the scope most of its
    users carry, else most of its operands."""
    comps = parse_computations(text)
    out: Dict[str, str] = {}
    bare: List[Op] = []
    for ops in comps.values():
        for op in ops:
            m = _OP_NAME.search(op.line)
            if m is None:
                bare.append(op)
                continue
            scope = _scope_of(m.group(1), names)
            if scope is not None:
                out[op.name] = scope
    for ops in comps.values():
        for op in ops:
            if op.opcode == "fusion" and op.name not in out:
                cm = _CALLS.search(op.line)
                inner = comps.get(cm.group(1), []) if cm else []
                _vote(out, op.name, [i.name for i in inner])
    users: Dict[str, List[str]] = {}
    for ops in comps.values():
        for op in ops:
            for o in _operands(op.line):
                users.setdefault(o, []).append(op.name)
    for op in bare:
        if op.name not in out and not _vote(out, op.name, users.get(op.name, [])):
            _vote(out, op.name, _operands(op.line))
    return out


def _vote(scopes: Dict[str, str], name: str, others: List[str]) -> bool:
    """Give ``name`` the scope most of ``others`` carry, if any carries one."""
    votes: Dict[str, int] = {}
    for o in others:
        if o in scopes:
            votes[scopes[o]] = votes.get(scopes[o], 0) + 1
    if votes:
        scopes[name] = max(sorted(votes), key=votes.get)
    return bool(votes)


def fused_work_scopes(text: str, names) -> Dict[str, frozenset]:
    """{fusion name: the scopes of the dots and convolutions it computes}
    for each fusion that computes any, nested fusions included.  A fusion
    whose work spans several scopes is one the compiler made across scope
    boundaries (a projection fused into the attention's product)."""
    comps = parse_computations(text)
    where = op_scopes(text, names)
    memo: Dict[str, frozenset] = {}

    def work(cname: str) -> frozenset:
        if cname not in memo:
            acc = set()
            for op in comps.get(cname, []):
                if op.opcode in ("dot", "convolution") and op.name in where:
                    acc.add(where[op.name])
                cm = _CALLS.search(op.line)
                if cm:
                    acc |= work(cm.group(1))
            memo[cname] = frozenset(acc)
        return memo[cname]

    out: Dict[str, frozenset] = {}
    for ops in comps.values():
        for op in ops:
            cm = _CALLS.search(op.line) if op.opcode == "fusion" else None
            if cm and work(cm.group(1)):
                out[op.name] = work(cm.group(1))
    return out


def scope_flops(text: str, names) -> Dict[Optional[str], float]:
    """FLOPs of every ``dot`` and ``convolution`` per scope (``None`` for
    those outside every scope), each times its loop multiplier, as
    ``module_costs`` counts them."""
    comps = parse_computations(text)
    mult = loop_multipliers(text, comps)
    table = _symbol_table(comps)
    scopes = op_scopes(text, names)
    out: Dict[Optional[str], float] = {}
    for cname, ops in comps.items():
        for op in ops:
            if op.opcode == "dot":
                f = _dot_flops(op, table)
            elif op.opcode == "convolution":
                f = _conv_flops(op, table)
            else:
                continue
            key = scopes.get(op.name)
            out[key] = out.get(key, 0.0) + mult.get(cname, 1.0) * f
    return out
