"""Trip-count-aware FLOP / HBM-byte accounting from optimized HLO.

``compiled.cost_analysis()`` (CPU backend) counts a while-loop body once,
which under-counts a 126-layer lax.scan by 126x. We walk the compiled,
SPMD-partitioned HLO module ourselves (so all numbers are PER DEVICE) and
weight by loop trip counts recovered from loop conditions:

* FLOPs: every ``dot`` contributes 2 * prod(result dims) * prod(lhs
  contracting dims); ``convolution`` contributes 2 * prod(result) *
  prod(kernel non-output dims). Dots inside fusion bodies count too.
* HBM bytes: sum of (result + operand) bytes of top-level instructions in
  non-fusion computations — the "perfect fusion" HBM-traffic model; fused
  internals never touch HBM. parameter/constant/gte/tuple/bitcast lines
  are skipped. Operand shapes come from a per-computation symbol table
  (params + instruction results).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from repro.roofline.hlo import DTYPE_BYTES, _ARRAY_RE, _CONST_RE

_OPCODE_RE = re.compile(r"([a-z][a-z0-9\-\.]*)\(")
_INSTR_RE = re.compile(r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_ATTR_COMP_RE = re.compile(r"(?:to_apply|calls|body|condition|branch_computations)=")

SKIP_BYTES = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "iota", "partition-id", "replica-id",
}


def _arrays(text: str):
    out = []
    for dtype, dims in _ARRAY_RE.findall(text):
        if dtype not in DTYPE_BYTES:
            continue
        shape = [int(d) for d in dims.split(",") if d]
        out.append((dtype, shape))
    return out


def _type_bytes(text: str) -> int:
    total = 0
    for dtype, shape in _arrays(text):
        n = 1
        for d in shape:
            n *= d
        total += n * DTYPE_BYTES[dtype]
    return total


@dataclass
class Instr:
    name: str
    opcode: str
    result_type: str
    operands: list
    attrs: str
    line: str


@dataclass
class Computation:
    name: str
    instrs: list = field(default_factory=list)
    symtab: dict = field(default_factory=dict)  # name -> result type str


def parse_module(hlo: str):
    """Returns (dict name -> Computation, entry name)."""
    comps: dict[str, Computation] = {}
    current = None
    entry = None
    for raw in hlo.splitlines():
        line = raw.strip()
        if not line:
            continue
        is_header = (
            not raw.startswith(" ") and line.endswith("{") and "->" in line
        )
        if is_header:
            head = line[5:].strip() if line.startswith("ENTRY") else line
            name = head.split("(")[0].strip().lstrip("%").strip()
            current = Computation(name)
            comps[name] = current
            if line.startswith("ENTRY"):
                entry = name
            # parameters: "(p0: f32[2,3], p1: (f32[2], s32[]))"
            if "(" in head:
                params_str = head[head.index("(") + 1 : head.rindex("->")]
                for m in re.finditer(r"([\w.\-]+)\s*:\s*", params_str):
                    pname = m.group(1)
                    rest = params_str[m.end() :]
                    nxt = re.search(r"[\w.\-]+\s*:", rest)
                    tstr = rest[: nxt.start()] if nxt else rest
                    current.symtab[pname] = tstr
            continue
        if line == "}" or current is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        om = _OPCODE_RE.search(rhs)
        if not om:
            continue
        opcode = om.group(1)
        result_type = rhs[: om.start()]
        after = rhs[om.end() :]
        # operand region: up to the matching close paren (assume flat)
        close = after.find(")")
        operand_str = after[:close] if close >= 0 else after
        attrs = after[close + 1 :] if close >= 0 else ""
        operands = _OPERAND_RE.findall(operand_str)
        instr = Instr(name, opcode, result_type, operands, attrs, line)
        current.instrs.append(instr)
        current.symtab[name] = result_type
    return comps, entry


def _trip_count_of(comps, cond_name: str) -> int:
    comp = comps.get(cond_name)
    if comp is None:
        return 1
    best = 1
    for ins in comp.instrs:
        for c in _CONST_RE.findall(ins.line):
            best = max(best, int(c))
    return best


def _attr_comp(ins: Instr, key: str):
    m = re.search(key + r"=%?([\w.\-]+)", ins.line)
    return m.group(1) if m else None


def _dot_flops(ins: Instr, symtab) -> float:
    res = _arrays(ins.result_type)
    if not res:
        return 0.0
    n = 1
    for d in res[0][1]:
        n *= d
    lhs_t = symtab.get(ins.operands[0], "") if ins.operands else ""
    lhs = _arrays(lhs_t)
    if not lhs:
        return 0.0
    m = _LHS_CONTRACT_RE.search(ins.line)
    contract = [int(d) for d in m.group(1).split(",") if d] if m else []
    k = 1
    for d in contract:
        if d < len(lhs[0][1]):
            k *= lhs[0][1][d]
    return 2.0 * n * k


def _conv_flops(ins: Instr, symtab) -> float:
    res = _arrays(ins.result_type)
    if not res or len(ins.operands) < 2:
        return 0.0
    n = 1
    for d in res[0][1]:
        n *= d
    ker = _arrays(symtab.get(ins.operands[1], ""))
    if not ker:
        return 0.0
    k = 1
    for d in ker[0][1][:-1]:
        k *= d
    return 2.0 * n * k


def _instr_bytes(ins: Instr, symtab, comps=None) -> float:
    """HBM traffic of one instruction.

    Slicing ops only touch the sliced region, NOT the whole operand —
    charging a scan's dynamic-update-slice the full stacked output buffer
    every iteration would overcount a 4096-step scan by orders of
    magnitude (caught against the xLSTM scan).
    XLA wraps the dus in a kLoop fusion whose *result type* is the full
    aliased buffer, so fusions are resolved through their root.
    """
    res = _type_bytes(ins.result_type)
    if ins.opcode in ("dynamic-slice", "slice", "gather"):
        return 2.0 * res  # read slice region + write result
    if ins.opcode == "dynamic-update-slice":
        upd = symtab.get(ins.operands[1], "") if len(ins.operands) > 1 else ""
        return 2.0 * _type_bytes(upd)
    if ins.opcode == "scatter":
        upd = symtab.get(ins.operands[-1], "") if ins.operands else ""
        return 3.0 * _type_bytes(upd)
    if ins.opcode == "fusion" and comps is not None:
        return _fusion_bytes(ins, symtab, comps)
    b = res
    for op in ins.operands:
        b += _type_bytes(symtab.get(op, ""))
    return b


def _fusion_bytes(ins: Instr, symtab, comps) -> float:
    """Fusion traffic: operands (excluding in-place-aliased full buffers)
    + outputs, where a dynamic-update-slice root writes only its update
    region."""
    tgt = _attr_comp(ins, "calls")
    comp = comps.get(tgt) if tgt else None

    def out_bytes_of(name, fcomp):
        node = next((i for i in fcomp.instrs if i.name == name), None)
        if node is None:
            return _type_bytes(fcomp.symtab.get(name, ""))
        if node.opcode == "dynamic-update-slice" and len(node.operands) > 1:
            return 2.0 * _type_bytes(fcomp.symtab.get(node.operands[1], ""))
        return _type_bytes(node.result_type)

    if comp is not None and comp.instrs:
        root = comp.instrs[-1]
        if root.opcode == "tuple":
            out = sum(out_bytes_of(op, comp) for op in root.operands)
        else:
            out = out_bytes_of(root.name, comp)
        aliased = root.opcode == "dynamic-update-slice" or (
            root.opcode == "tuple"
            and any(
                (n := next((i for i in comp.instrs if i.name == op), None))
                and n.opcode == "dynamic-update-slice"
                for op in root.operands
            )
        )
    else:
        out = _type_bytes(ins.result_type)
        aliased = "dynamic-update-slice" in ins.name
        if aliased:
            out = 0.0  # cannot resolve update size; be conservative
    inp = 0.0
    for op in ins.operands:
        t = symtab.get(op, "")
        # in-place dus fusions alias the big output buffer as an operand;
        # it is not read in full
        if aliased and t and t.strip() == ins.result_type.strip():
            continue
        inp += _type_bytes(t)
    return inp + out


@dataclass
class HloCost:
    flops: float
    bytes: float
    by_comp_flops: dict


def hlo_cost(hlo: str) -> HloCost:
    comps, entry = parse_module(hlo)
    if entry is None:
        entry = next(iter(comps)) if comps else None
    if entry is None:
        return HloCost(0.0, 0.0, {})

    fusion_bodies = set()
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.opcode == "fusion":
                tgt = _attr_comp(ins, "calls")
                if tgt:
                    fusion_bodies.add(tgt)

    flops_cache: dict[str, float] = {}

    def comp_flops(cname: str, seen=()) -> float:
        """Total FLOPs of one call of computation cname (nested weighted)."""
        if cname in flops_cache:
            return flops_cache[cname]
        comp = comps.get(cname)
        if comp is None or cname in seen:
            return 0.0
        f = 0.0
        for ins in comp.instrs:
            if ins.opcode == "dot":
                f += _dot_flops(ins, comp.symtab)
            elif ins.opcode == "convolution":
                f += _conv_flops(ins, comp.symtab)
            elif ins.opcode == "while":
                body = _attr_comp(ins, "body")
                cond = _attr_comp(ins, "condition")
                trips = _trip_count_of(comps, cond) if cond else 1
                f += trips * comp_flops(body, seen + (cname,))
            elif ins.opcode in ("fusion", "call", "conditional", "custom-call"):
                for key in ("calls", "to_apply"):
                    tgt = _attr_comp(ins, key)
                    if tgt:
                        f += comp_flops(tgt, seen + (cname,))
                        break
        flops_cache[cname] = f
        return f

    total_bytes = [0.0]
    by_comp: dict[str, float] = {}

    def walk_bytes(cname: str, mult: float, seen=()):
        comp = comps.get(cname)
        if comp is None or cname in seen:
            return
        for ins in comp.instrs:
            if ins.opcode == "while":
                body = _attr_comp(ins, "body")
                cond = _attr_comp(ins, "condition")
                trips = _trip_count_of(comps, cond) if cond else 1
                if body:
                    walk_bytes(body, mult * trips, seen + (cname,))
                continue
            if ins.opcode == "call":
                tgt = _attr_comp(ins, "to_apply")
                if tgt and tgt not in fusion_bodies:
                    walk_bytes(tgt, mult, seen + (cname,))
                    continue
            if ins.opcode in SKIP_BYTES:
                continue
            total_bytes[0] += mult * _instr_bytes(ins, comp.symtab, comps)

    def walk_flops(cname: str, mult: float, seen=()):
        comp = comps.get(cname)
        if comp is None or cname in seen:
            return
        for ins in comp.instrs:
            if ins.opcode == "while":
                body = _attr_comp(ins, "body")
                cond = _attr_comp(ins, "condition")
                trips = _trip_count_of(comps, cond) if cond else 1
                if body:
                    walk_flops(body, mult * trips, seen + (cname,))
                continue
            f = 0.0
            if ins.opcode == "dot":
                f = _dot_flops(ins, comp.symtab)
            elif ins.opcode == "convolution":
                f = _conv_flops(ins, comp.symtab)
            elif ins.opcode in ("fusion", "call", "conditional"):
                for key in ("calls", "to_apply"):
                    tgt = _attr_comp(ins, key)
                    if tgt:
                        f = comp_flops(tgt, seen + (cname,))
                        break
            if f:
                by_comp[cname] = by_comp.get(cname, 0.0) + mult * f

    walk_flops(entry, 1.0)
    walk_bytes(entry, 1.0)
    total_flops = sum(by_comp.values())
    return HloCost(total_flops, total_bytes[0], by_comp)


# ---------------------------------------------------------------------------
# compiled cost of a jitted function (AOT: no device allocation)
# ---------------------------------------------------------------------------


@dataclass
class JitCost:
    """Compiled-program cost of one jitted function.

    hbm_bytes        trip-count-weighted HBM traffic of the optimized HLO
                     (the hlo_cost model above)
    flops            same walk, dot/conv FLOPs
    arg_bytes        total input buffer bytes
    out_bytes        total output buffer bytes
    alias_bytes      bytes of inputs aliased onto outputs (buffer
                     donation — jax.jit(donate_argnums=...)); these
                     buffers are counted once, not twice
    temp_bytes       compiler temp allocation
    peak_state_bytes arg + out + temp - alias: the peak live footprint of
                     the program's own buffers, the number donation
                     halves for a state -> state step (DESIGN.md §10)
    """

    hbm_bytes: float
    flops: float
    arg_bytes: int
    out_bytes: int
    alias_bytes: int
    temp_bytes: int

    @property
    def peak_state_bytes(self) -> int:
        return (self.arg_bytes + self.out_bytes + self.temp_bytes
                - self.alias_bytes)


def jit_cost(fn, *abstract_args, **jit_kwargs) -> JitCost:
    """Lower + compile ``fn`` on abstract ShapeDtypeStruct args and read
    the costs off the compiled artifact — nothing is allocated or run, so
    this works at full width on the CPU. ``jit_kwargs`` pass to jax.jit;
    ``donate_argnums`` is how the donated-vs-functional peak-memory
    comparison is produced."""
    import jax

    compiled = jax.jit(fn, **jit_kwargs).lower(*abstract_args).compile()
    cost = hlo_cost(compiled.as_text())
    mem = compiled.memory_analysis()
    return JitCost(
        hbm_bytes=cost.bytes,
        flops=cost.flops,
        arg_bytes=int(mem.argument_size_in_bytes),
        out_bytes=int(mem.output_size_in_bytes),
        alias_bytes=int(mem.alias_size_in_bytes),
        temp_bytes=int(mem.temp_size_in_bytes),
    )
