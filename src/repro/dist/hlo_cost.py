"""Trip-count-aware flops/bytes walker over optimized HLO text.

``Compiled.cost_analysis()`` visits every computation ONCE, so a scanned
88-layer model reports one layer's flops — useless for roofline math on
scan-over-layers programs.  ``analyze_hlo`` re-derives the counts from the
optimized HLO text instead, multiplying ``while`` body/condition costs by the
trip count XLA annotates (``backend_config={"known_trip_count":{"n":...}}``,
emitted after loop canonicalisation; an unannotated loop conservatively
counts once).

Counting rules mirror ``HloCostAnalysis`` closely enough to land within a few
percent of XLA on loop-free programs (tests assert <5%):

* dot           2 * |out| * |contracted dims|
* convolution   2 * |out| * |kernel| / output-feature dim
* elementwise   |out| flops (transcendentals tracked separately, like XLA)
* reduce        |in| - |out|
* fusion        inner flops recursively; bytes at the fusion boundary only
* collectives   zero flops; wire bytes via ``hlo_analysis.CollectiveOp``

The module parser is intentionally text-level (no xla_client dependency): it
runs on saved ``*.hlo.txt`` artifacts from past dry-runs as well as live
``compiled.as_text()`` output.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from repro.dist.hlo_analysis import CollectiveOp

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[([\d,]+)\]<=")

# elementwise ops billed at one flop per output element (XLA's default)
_FLOP1 = {
    "add", "subtract", "multiply", "divide", "remainder", "maximum",
    "minimum", "negate", "abs", "sign", "compare", "and", "or", "xor", "not",
    "select", "shift-left", "shift-right-arithmetic", "shift-right-logical",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "is-finite",
}
# billed as transcendentals, NOT flops (matches XLA's 'flops' property)
_TRANSCENDENTAL = {
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "logistic", "tanh", "tan", "sine", "cosine", "sqrt", "rsqrt", "cbrt",
    "power", "atan2", "erf",
}
# pure data movement / bookkeeping: zero flops, zero bytes charged
_FREE = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "rng-get-and-update-state",
    "opt-barrier", "optimization-barrier", "domain",
}
# data movement billed by bytes only
_MOVE = {
    "copy", "reshape", "transpose", "broadcast", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "reverse", "gather",
    "convert", "bitcast-convert", "select-and-scatter", "sort", "rng",
    "rng-bit-generator", "custom-call", "clamp", "map", "real", "imag",
    "stochastic-convert", "reduce-precision", "copy-start", "copy-done",
}

_COLLECTIVE_BASES = ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute")


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    out_shapes: List[Tuple[str, Tuple[int, ...]]]
    operand_text: str
    attrs: str
    # operand shapes, resolved through the computation's names when the
    # text prints bare operand names (current XLA) rather than typed ones
    operand_shapes: List[Tuple[str, Tuple[int, ...]]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class Module:
    computations: Dict[str, List[Instr]]
    entry: str
    num_partitions: int
    num_replicas: int


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    transcendentals: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0

    def __iadd__(self, o: "Cost") -> "Cost":
        self.flops += o.flops
        self.transcendentals += o.transcendentals
        self.bytes += o.bytes
        self.collective_bytes += o.collective_bytes
        return self

    def scaled(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.transcendentals * k,
                    self.bytes * k, self.collective_bytes * k)


# ---------------------------------------------------------------------------
# text parsing


def _shapes_of(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES and dtype not in ("token", "opaque"):
            continue
        shape = tuple(int(d) for d in dims.split(",")) if dims else ()
        out.append((dtype, shape))
    return out


def _elems(shape: Tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _bytes(shapes: List[Tuple[str, Tuple[int, ...]]]) -> float:
    return float(sum(_DTYPE_BYTES.get(dt, 0) * _elems(sh)
                     for dt, sh in shapes))


def _split_balanced(text: str, open_at: int) -> Tuple[str, str]:
    """text[open_at] == '(' -> (inside, remainder-after-matching-close)."""
    depth = 0
    for i in range(open_at, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return text[open_at + 1:i], text[i + 1:]
    return text[open_at + 1:], ""


def _parse_instr(line: str) -> Optional[Instr]:
    m = _INSTR_RE.match(line)
    if m is None:
        return None
    name, rhs = m.group(1), m.group(2).strip()
    # result type: a (possibly tuple) shape token
    if rhs.startswith("("):
        type_str, rest = _split_balanced(rhs, 0)
        rest = rest.lstrip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        type_str, rest = rhs[:sp], rhs[sp + 1:].lstrip()
    om = re.match(r"([\w\-]+)\(", rest)
    if om is None:
        return None
    opcode = om.group(1)
    operand_text, attrs = _split_balanced(rest, om.end() - 1)
    return Instr(name=name, opcode=opcode, out_shapes=_shapes_of(type_str),
                 operand_text=operand_text, attrs=attrs)


def _resolve_operands(instr: Instr, names: Dict[str, list]) -> None:
    shapes = _shapes_of(instr.operand_text)
    if not shapes:
        for ref in re.findall(r"%([\w.\-$]+)", instr.operand_text):
            shapes.extend(names.get(ref, []))
    instr.operand_shapes = shapes


def parse_module(hlo_text: str) -> Module:
    comps: Dict[str, List[Instr]] = {}
    entry = ""
    current: Optional[List[Instr]] = None
    names: Dict[str, list] = {}
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        if not line or line.startswith("HloModule"):
            continue
        stripped = line.strip()
        if stripped.endswith("{") and "->" in stripped and " = " not in stripped:
            is_entry = stripped.startswith("ENTRY")
            head = stripped[len("ENTRY"):].strip() if is_entry else stripped
            nm = re.match(r"%?([\w.\-$]+)", head)
            if nm is None:
                continue
            current = comps.setdefault(nm.group(1), [])
            names = {}
            if is_entry:
                entry = nm.group(1)
            continue
        if stripped == "}":
            current = None
            continue
        if current is not None:
            instr = _parse_instr(line)
            if instr is not None:
                _resolve_operands(instr, names)
                names[instr.name] = instr.out_shapes
                current.append(instr)
    if not entry and comps:   # fall back: last computation is usually entry
        entry = list(comps)[-1]
    np_m = re.search(r"num_partitions=(\d+)", hlo_text)
    nr_m = re.search(r"replica_count=(\d+)|num_replicas=(\d+)", hlo_text)
    n_rep = 1
    if nr_m:
        n_rep = int(next(g for g in nr_m.groups() if g))
    return Module(computations=comps, entry=entry,
                  num_partitions=int(np_m.group(1)) if np_m else 1,
                  num_replicas=n_rep)


# ---------------------------------------------------------------------------
# per-instruction costs


def _attr_ref(attrs: str, key: str) -> Optional[str]:
    m = re.search(key + r"=%?([\w.\-$]+)", attrs)
    return m.group(1) if m else None


def _dims_attr(attrs: str, key: str) -> Tuple[int, ...]:
    m = re.search(key + r"=\{([\d,]*)\}", attrs)
    if m is None or not m.group(1):
        return ()
    return tuple(int(x) for x in m.group(1).split(","))


def group_size(instr: Instr, module: Module) -> int:
    m = _GROUPS_BRACE_RE.search(instr.attrs)
    if m:
        first = [g for g in m.group(1).split(",") if g.strip() != ""]
        if first:
            return len(first)
    m = _GROUPS_IOTA_RE.search(instr.attrs)
    if m:
        dims = [int(x) for x in m.group(1).split(",")]
        return dims[-1] if dims else 1
    return max(module.num_partitions, module.num_replicas)


def collective_of(instr: Instr, module: Module) -> Optional[CollectiveOp]:
    op = instr.opcode
    if op.endswith("-done"):
        return None     # counted at the matching -start
    base = next((b for b in _COLLECTIVE_BASES if op.startswith(b)), None)
    if base is None:
        return None
    if op.endswith("-start"):
        # async form: result is a tuple carrying the operand alongside the
        # transfer buffer (plus u32 context scalars) — pick the shape the
        # wire factor applies to instead of summing the whole tuple
        sizes = [_DTYPE_BYTES.get(dt, 0) * _elems(sh)
                 for dt, sh in instr.out_shapes
                 if not (dt in ("u32", "s32") and _elems(sh) <= 1)]
        if not sizes:
            return None
        b = min(sizes) if base == "reduce-scatter" else max(sizes)
        return CollectiveOp(base, float(b), group_size(instr, module))
    return CollectiveOp(base, _bytes(instr.out_shapes),
                        group_size(instr, module))


def _dot_flops(instr: Instr) -> float:
    out = sum(_elems(sh) for _, sh in instr.out_shapes)
    operands = instr.operand_shapes
    if not operands:
        return 0.0
    lhs_dims = operands[0][1]
    contract = _dims_attr(instr.attrs, "lhs_contracting_dims")
    k = 1
    for i in contract:
        if i < len(lhs_dims):
            k *= lhs_dims[i]
    return 2.0 * out * k


def _conv_flops(instr: Instr) -> float:
    out = sum(_elems(sh) for _, sh in instr.out_shapes)
    operands = instr.operand_shapes
    if len(operands) < 2:
        return 0.0
    kernel = operands[1][1]
    o_dim = len(kernel) - 1
    dm = re.search(r"dim_labels=[^\s,]*_([\w]+)->", instr.attrs)
    if dm and "o" in dm.group(1):
        o_dim = dm.group(1).index("o")
    k = 1
    for i, d in enumerate(kernel):
        if i != o_dim:
            k *= d
    return 2.0 * out * k


def _window_elems(attrs: str) -> int:
    m = re.search(r"window=\{[^}]*size=([\dx]+)", attrs)
    if not m:
        return 1
    n = 1
    for d in m.group(1).split("x"):
        n *= int(d)
    return n


def _instr_cost(instr: Instr, module: Module,
                memo: Dict[str, Cost]) -> Cost:
    op = instr.opcode
    out_elems = sum(_elems(sh) for _, sh in instr.out_shapes)
    out_bytes = _bytes(instr.out_shapes)
    operand_bytes = _bytes(instr.operand_shapes)
    io_bytes = operand_bytes + out_bytes

    if op in _FREE:
        return Cost()
    if op == "while":
        body = _attr_ref(instr.attrs, "body")
        cond = _attr_ref(instr.attrs, "condition")
        trips_m = _TRIP_RE.search(instr.attrs)
        trips = int(trips_m.group(1)) if trips_m else 1
        inner = Cost()
        for ref in (body, cond):
            if ref:
                inner += _computation_cost(ref, module, memo)
        return inner.scaled(trips)
    if op == "conditional":
        refs = re.findall(r"%?([\w.\-$]+)", instr.attrs)
        names = [r for r in refs if r in module.computations]
        total = Cost()
        for ref in names:
            total += _computation_cost(ref, module, memo)
        return total
    if op == "fusion":
        ref = _attr_ref(instr.attrs, "calls")
        inner = _computation_cost(ref, module, memo) if ref else Cost()
        # bytes cross the fusion boundary only; inner bytes stay in registers
        return Cost(inner.flops, inner.transcendentals, io_bytes,
                    inner.collective_bytes)
    if op == "call":
        ref = _attr_ref(instr.attrs, "to_apply")
        return _computation_cost(ref, module, memo) if ref else Cost()

    coll = collective_of(instr, module)
    if coll is not None:
        return Cost(bytes=io_bytes, collective_bytes=coll.wire_bytes)
    if op.endswith("-done") or op == "send" or op == "recv":
        return Cost()

    if op == "dot":
        return Cost(flops=_dot_flops(instr), bytes=io_bytes)
    if op == "convolution":
        return Cost(flops=_conv_flops(instr), bytes=io_bytes)
    if op == "reduce":
        in_elems = sum(_elems(sh) for _, sh in instr.operand_shapes)
        return Cost(flops=float(max(in_elems - out_elems, 0)), bytes=io_bytes)
    if op == "reduce-window":
        return Cost(flops=float(out_elems * max(_window_elems(instr.attrs) - 1, 1)),
                    bytes=io_bytes)
    if op == "scatter":
        operands = instr.operand_shapes
        upd = _elems(operands[-1][1]) if operands else 0
        return Cost(flops=float(upd), bytes=io_bytes)
    if op in _TRANSCENDENTAL:
        return Cost(transcendentals=float(out_elems), bytes=io_bytes)
    if op in _FLOP1:
        return Cost(flops=float(out_elems), bytes=io_bytes)
    if op in _MOVE:
        return Cost(bytes=io_bytes)
    # unknown opcode: charge data movement only
    return Cost(bytes=io_bytes)


def _computation_cost(name: str, module: Module,
                      memo: Dict[str, Cost]) -> Cost:
    if name in memo:
        return memo[name]
    memo[name] = Cost()   # cycle guard (malformed input)
    total = Cost()
    for instr in module.computations.get(name, []):
        total += _instr_cost(instr, module, memo)
    memo[name] = total
    return total


# ---------------------------------------------------------------------------
# public entry point


def analyze_hlo(hlo_text: str) -> Dict[str, float]:
    """Walk optimized HLO text -> trip-count-aware cost summary.

    Returns ``{"flops", "transcendentals", "bytes", "collective_bytes"}``,
    all per-device (the SPMD module is the per-device program).
    """
    module = parse_module(hlo_text)
    cost = _computation_cost(module.entry, module, {})
    return {
        "flops": cost.flops,
        "transcendentals": cost.transcendentals,
        "bytes": cost.bytes,
        "collective_bytes": cost.collective_bytes,
    }
