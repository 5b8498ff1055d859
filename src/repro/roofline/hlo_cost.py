"""Trip-count-aware cost model over compiled HLO text.

Why this exists: ``compiled.cost_analysis()`` visits every computation
ONCE — ``while`` bodies (every ``lax.scan``: layers, grad-accum, remat,
time chunks) are not multiplied by their trip counts, so a scanned
transformer under-reports FLOPs by ~n_layers × accum, and collectives
inside scanned bodies are invisible to a flat text scan.  The compiled
CPU/TPU HLO carries ``backend_config={"known_trip_count":{"n":...}}`` on
each while; this module parses the module text into a computation graph and
walks it multiplying by trip counts.

Counted:
  * FLOPs   — dot: 2·|result|·K (K = product of contracting dims);
              arithmetic elementwise: 1·|result|; transcendentals tracked
              separately.
  * bytes   — per instruction: operand + result bytes (fusion nodes count
              their boundary only, like XLA's bytes-accessed), whiles
              multiply bodies.
  * collectives — operand bytes per kind (all-gather / all-reduce /
              reduce-scatter / all-to-all / collective-permute), trip-aware.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "token": 0, "opaque": 0,
}

_ARITH_OPS = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "compare", "select", "and", "or", "xor", "not", "clamp",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even", "sign",
    "remainder", "atan2", "popcnt", "shift-left", "shift-right-logical",
    "shift-right-arithmetic",
}
_TRANSCENDENTAL_OPS = {
    "exponential", "log", "log-plus-one", "exponential-minus-one", "tanh",
    "rsqrt", "sqrt", "cbrt", "power", "sine", "cosine", "tan", "logistic",
    "erf", "expm1",
}
_REDUCE_OPS = {"reduce", "reduce-window"}
_ZERO_COST = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "reshape", "broadcast", "transpose", "copy", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "iota", "convert", "pad",
    "reverse", "gather", "scatter", "after-all", "partition-id",
    "replica-id", "optimization-barrier", "copy-start", "copy-done",
    "bitcast-convert", "rng-bit-generator", "reduce-precision", "sort",
    "custom-call", "infeed", "outfeed", "domain", "send", "recv",
    "send-done", "recv-done", "add-dependency",
}
COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")


@dataclasses.dataclass
class Shape:
    dtype: str
    dims: tuple[int, ...]
    is_tuple: bool = False
    elements: tuple["Shape", ...] = ()

    @property
    def n_elem(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def bytes(self) -> int:
        if self.is_tuple:
            return sum(e.bytes for e in self.elements)
        return self.n_elem * _DTYPE_BYTES.get(self.dtype, 0)


_ARRAY_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _parse_array(s: str) -> Shape | None:
    m = _ARRAY_RE.match(s.strip())
    if not m:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",")) if m.group(2) else ()
    return Shape(m.group(1), dims)


def _split_top(s: str) -> list[str]:
    """Split on commas at paren/brace depth 0."""
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return parts


def _parse_type(s: str) -> tuple[Shape | None, str]:
    """Parse a type at the start of ``s``; returns (shape, rest)."""
    s = s.lstrip()
    if s.startswith("("):
        depth = 0
        for i, ch in enumerate(s):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                inner = s[1:i]
                elems = []
                for part in _split_top(inner):
                    e, _ = _parse_type(part)
                    if e:
                        elems.append(e)
                return (Shape("tuple", (), True, tuple(elems)), s[i + 1:])
        return None, s
    m = _ARRAY_RE.match(s)
    if not m:
        return None, s
    rest = s[m.end():]
    # skip layout '{...}' suffix
    if rest.startswith("{"):
        rest = rest[rest.index("}") + 1:]
    dims = tuple(int(d) for d in m.group(2).split(",")) if m.group(2) else ()
    return Shape(m.group(1), dims), rest


@dataclasses.dataclass
class Instr:
    name: str
    shape: Shape
    opcode: str
    operands: tuple[str, ...]
    attrs: str
    raw_operands: str = ""

    def param_index(self) -> int | None:
        if self.opcode != "parameter":
            return None
        m = re.match(r"\s*(\d+)", self.raw_operands)
        return int(m.group(1)) if m else None

    def attr_calls(self) -> str | None:
        m = re.search(r"calls=%?([\w.\-]+)", self.attrs)
        return m.group(1) if m else None

    def attr_body(self) -> str | None:
        m = re.search(r"body=%?([\w.\-]+)", self.attrs)
        return m.group(1) if m else None

    def trip_count(self) -> int:
        m = re.search(r'known_trip_count\\?":{\\?"n\\?":\\?"(\d+)', self.attrs)
        return int(m.group(1)) if m else 1

    def contracting(self) -> tuple[int, ...]:
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", self.attrs)
        if not m or not m.group(1):
            return ()
        return tuple(int(d) for d in m.group(1).split(","))


_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)\s*$")

_INSTR_LINE_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{")


@dataclasses.dataclass
class Computation:
    name: str
    instrs: dict[str, Instr]
    is_entry: bool = False


def parse_module(text: str) -> tuple[dict[str, Computation], str]:
    """Parse compiled HLO text; returns (computations, entry_name)."""
    comps: dict[str, Computation] = {}
    entry = ""
    cur: Computation | None = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HEADER_RE.match(line.strip())
            if m and "=" not in line.split("(")[0]:
                cur = Computation(m.group(1), {},
                                  line.lstrip().startswith("ENTRY"))
                if cur.is_entry:
                    entry = cur.name
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _INSTR_LINE_RE.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        shape, rest = _parse_type(rest)
        if shape is None:
            continue
        rest = rest.strip()
        om = re.match(r"([\w\-]+)\((.*)$", rest)
        if not om:
            continue
        opcode = om.group(1)
        # operand list: up to matching close paren
        tail = om.group(2)
        depth = 1
        for i, ch in enumerate(tail):
            depth += ch in "("
            depth -= ch in ")"
            if depth == 0:
                ops_raw, attrs = tail[:i], tail[i + 1:]
                break
        else:
            ops_raw, attrs = tail, ""
        operands = []
        for part in _split_top(ops_raw):
            mo = _OPERAND_NAME_RE.search(part.strip())
            if mo:
                operands.append(mo.group(1))
        cur.instrs[name] = Instr(name, shape, opcode, tuple(operands), attrs,
                                 ops_raw)
    return comps, entry


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    transcendentals: float = 0.0
    bytes: float = 0.0
    coll_bytes: dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVE_OPS})
    coll_count: int = 0

    def __iadd__(self, o: "Cost") -> "Cost":
        self.flops += o.flops
        self.transcendentals += o.transcendentals
        self.bytes += o.bytes
        for k in self.coll_bytes:
            self.coll_bytes[k] += o.coll_bytes[k]
        self.coll_count += o.coll_count
        return self

    def scaled(self, f: float) -> "Cost":
        return Cost(self.flops * f, self.transcendentals * f, self.bytes * f,
                    {k: v * f for k, v in self.coll_bytes.items()},
                    int(self.coll_count * f))

    @property
    def total_coll_bytes(self) -> float:
        return sum(self.coll_bytes.values())


class HloCostModel:
    def __init__(self, text: str):
        self.comps, self.entry = parse_module(text)
        self._memo: dict[tuple[str, bool], Cost] = {}

    # ------------------------------------------------------------------
    def _operand_shape(self, comp: Computation, name: str) -> Shape | None:
        ins = comp.instrs.get(name)
        return ins.shape if ins else None

    def _instr_cost(self, comp: Computation, ins: Instr, fused: bool) -> Cost:
        c = Cost()
        op = ins.opcode
        base = op.replace("-start", "").replace("-done", "")

        if base in COLLECTIVE_OPS:
            if op.endswith("-done"):
                return c
            b = 0.0
            for o in ins.operands:
                sh = self._operand_shape(comp, o)
                if sh is not None and not sh.is_tuple:
                    b += sh.bytes
            if b == 0.0 and not ins.shape.is_tuple:
                b = ins.shape.bytes
            c.coll_bytes[base] += b
            c.coll_count += 1
            c.bytes += b + (0 if ins.shape.is_tuple else ins.shape.bytes)
            return c

        if op == "while":
            body = ins.attr_body()
            trips = ins.trip_count()
            if body in self.comps:
                c += self.comp_cost(body).scaled(trips)
            return c

        if op in ("fusion", "call"):
            callee = ins.attr_calls()
            if callee in self.comps:
                inner = self.comp_cost(callee, fused=(op == "fusion"))
                c += inner
            if op == "fusion" and callee in self.comps:
                c.bytes += self._fusion_boundary_bytes(comp, ins, callee)
            elif op == "fusion":
                b = sum(sh.bytes for o in ins.operands
                        if (sh := self._operand_shape(comp, o)) is not None
                        and not sh.is_tuple)
                c.bytes += b + ins.shape.bytes
            return c

        if op == "conditional":
            # count the most expensive branch (upper bound)
            best = Cost()
            for m in re.finditer(r"(?:branch_computations=\{([^}]*)\}|"
                                 r"(?:true|false)_computation=%?([\w.\-]+))",
                                 ins.attrs):
                names = []
                if m.group(1):
                    names = [n.strip().lstrip("%")
                             for n in m.group(1).split(",")]
                elif m.group(2):
                    names = [m.group(2)]
                for n in names:
                    if n in self.comps:
                        bc = self.comp_cost(n)
                        if bc.flops >= best.flops:
                            best = bc
            c += best
            return c

        if op == "dot":
            k = 1
            lhs = (self._operand_shape(comp, ins.operands[0])
                   if ins.operands else None)
            for d in ins.contracting():
                if lhs is not None and d < len(lhs.dims):
                    k *= lhs.dims[d]
            c.flops += 2.0 * ins.shape.n_elem * k
        elif base in _TRANSCENDENTAL_OPS:
            c.transcendentals += ins.shape.n_elem
            c.flops += ins.shape.n_elem
        elif base in _ARITH_OPS:
            c.flops += ins.shape.n_elem
        elif base in _REDUCE_OPS:
            # elements reduced ~ operand size
            for o in ins.operands[:1]:
                sh = self._operand_shape(comp, o)
                if sh is not None and not sh.is_tuple:
                    c.flops += sh.n_elem

        # memory-level bytes only for non-fused instructions
        if fused:
            return c
        if op == "dynamic-slice":
            c.bytes += 2.0 * (0 if ins.shape.is_tuple else ins.shape.bytes)
        elif op == "dynamic-update-slice":
            upd = (self._operand_shape(comp, ins.operands[1])
                   if len(ins.operands) > 1 else None)
            c.bytes += 2.0 * (upd.bytes if upd is not None else 0.0)
        elif (op == "dot" or base in _ARITH_OPS
              or base in _TRANSCENDENTAL_OPS or base in _REDUCE_OPS
              or base in ("copy", "convert", "gather", "scatter",
                          "concatenate", "broadcast", "transpose")):
            b = ins.shape.bytes if not ins.shape.is_tuple else 0.0
            for o in ins.operands:
                sh = self._operand_shape(comp, o)
                if sh is not None and not sh.is_tuple:
                    b += sh.bytes
            c.bytes += b
        return c

    # ------------------------------------------------------------------
    def _param_utilized_bytes(self, callee: Computation, index: int,
                              full: Shape) -> float:
        """Bytes a fusion actually touches of parameter ``index``.

        XLA's bytes-accessed counts *slice* sizes for dynamic-slice /
        dynamic-update-slice — crucial for scan-stacked buffers (params,
        saved residuals) that each iteration only slices one layer out of.
        """
        if full.is_tuple:
            return 0.0
        # associate the fusion operand with the fused computation's
        # parameter by position; fall back to unique shape match.
        params = [i for i in callee.instrs.values()
                  if i.opcode == "parameter"]
        cands = [i for i in params if i.param_index() == index]
        if not cands:
            cands = [i for i in params if i.shape.dims == full.dims
                     and i.shape.dtype == full.dtype]
        if len(cands) != 1:
            return full.bytes
        pname = cands[0].name
        return min(self._utilized(callee, pname, full), full.bytes)

    def _utilized(self, callee: Computation, vname: str, full: Shape,
                  depth: int = 0) -> float:
        """Bytes touched of value ``vname`` given its consumers.

        dtype converts are transparent (a TPU's native-bf16 pipeline has no
        materialized legalization converts — the CPU backend's whole-buffer
        bf16↔f32 maintenance is excluded by design; DESIGN.md §9)."""
        consumers = [i for i in callee.instrs.values()
                     if vname in i.operands]
        if not consumers or depth > 3:
            return 0.0 if not consumers else full.bytes
        total = 0.0
        for cons in consumers:
            if cons.opcode in ("dynamic-slice", "slice"):
                total += cons.shape.bytes
            elif (cons.opcode == "dynamic-update-slice"
                  and cons.operands and cons.operands[0] == vname):
                # read-modify-write of the update region only
                upd = (self._operand_shape(callee, cons.operands[1])
                       if len(cons.operands) > 1 else None)
                total += upd.bytes if upd is not None else full.bytes
            elif cons.opcode in ("convert", "bitcast", "copy"):
                total += self._utilized(callee, cons.name, full, depth + 1)
            else:
                return full.bytes
        return total

    def _root_written_bytes(self, callee: Computation, full: float) -> float:
        """Bytes a fusion's root actually writes.

        If the root (through elementwise convert/copy/bitcast wrappers) is a
        dynamic-update-slice into a parameter, only the update region is
        written — the rest aliases the carried buffer (XLA in-place DUS).
        """
        root = None
        for i in callee.instrs.values():
            root = i        # printed HLO lists the root last
        cur = root
        hops = 0
        while (cur is not None and hops < 4
               and cur.opcode in ("convert", "copy", "bitcast", "reshape")
               and cur.operands):
            cur = callee.instrs.get(cur.operands[0])
            hops += 1
        if (cur is not None and cur.opcode == "dynamic-update-slice"
                and len(cur.operands) > 1):
            tgt = callee.instrs.get(cur.operands[0])
            upd = callee.instrs.get(cur.operands[1])
            hops = 0
            while (tgt is not None and hops < 4
                   and tgt.opcode in ("convert", "copy", "bitcast")
                   and tgt.operands):
                tgt = callee.instrs.get(tgt.operands[0])
                hops += 1
            if tgt is not None and tgt.opcode == "parameter" \
                    and upd is not None:
                return float(upd.shape.bytes)
        return full

    def _fusion_boundary_bytes(self, comp: Computation, ins: Instr,
                               callee_name: str) -> float:
        callee = self.comps[callee_name]
        b = 0.0
        for idx, o in enumerate(ins.operands):
            sh = self._operand_shape(comp, o)
            if sh is None or sh.is_tuple:
                continue
            b += self._param_utilized_bytes(callee, idx, sh)
        full = (ins.shape.bytes if not ins.shape.is_tuple
                else sum(e.bytes for e in ins.shape.elements))
        b += self._root_written_bytes(callee, float(full))
        return b

    def comp_cost(self, name: str, fused: bool = False) -> Cost:
        key = (name, fused)
        if key in self._memo:
            return self._memo[key]
        comp = self.comps[name]
        total = Cost()
        for ins in comp.instrs.values():
            total += self._instr_cost(comp, ins, fused)
        self._memo[key] = total
        return total

    def entry_cost(self) -> Cost:
        return self.comp_cost(self.entry)


def analyze(text: str) -> dict[str, Any]:
    """Full-module cost: flops / bytes / collective bytes, trip-aware."""
    model = HloCostModel(text)
    c = model.entry_cost()
    return {
        "flops": c.flops,
        "transcendentals": c.transcendentals,
        "bytes": c.bytes,
        "collective_bytes": c.total_coll_bytes,
        "collectives_by_kind": dict(c.coll_bytes),
        "collective_count": c.coll_count,
    }


def xla_cost_analysis(compiled) -> dict[str, Any]:
    """XLA's own ``compiled.cost_analysis()`` as a (possibly empty) dict —
    some backends return None.  The comparison baseline for this module's
    trip-aware numbers."""
    ca = compiled.cost_analysis()
    return dict(ca) if ca else {}
