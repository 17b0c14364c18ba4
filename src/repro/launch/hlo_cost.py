"""Trip-count-aware cost analysis over post-SPMD HLO text.

``compiled.cost_analysis()`` counts every computation ONCE — a
``lax.scan`` over 30 layer-repeats reports 1/30 of the real dot FLOPs
(verified in ``tests/test_roofline.py``).  Our models deliberately scan
over layer repeats and microbatches (small HLO, fast compiles), so the
dry-run needs its own analyzer:

* parse the module into computations + instructions,
* recursively evaluate cost over the call graph (fusion ``calls=``,
  ``while`` body/condition, conditional branches),
* multiply ``while`` bodies by the trip count recovered from the loop
  condition (scan lowers to ``compare(induction, constant), LT`` with
  a 0-start, 1-step counter),
* FLOPs from ``dot`` result/contraction shapes; HBM bytes from
  top-level operand+result sizes (fusions are the HBM-traffic units;
  instructions *inside* a fusion body touch registers/VMEM, not HBM);
  collective bytes from the operand shapes of every collective,
  bucketed by kind.

Validated against XLA's own numbers on unrolled graphs (where XLA is
correct) in ``tests/test_roofline.py``.
"""
from __future__ import annotations

import dataclasses
import re
import warnings
from typing import Dict, List, Optional, Tuple

from .. import compat


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """XLA's own per-device cost table for a compiled artifact.

    Read through ``compat.cost_analysis`` as a plain dict.  Kept here,
    next to the trip-count-aware analyzer it cross-checks.
    """
    return compat.cost_analysis(compiled)

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "ragged-all-to-all", "all-to-all", "collective-permute",
               "collective-broadcast")


def op_kind(op: str) -> str:
    """Normalize an HLO opcode to its collective kind.

    Async collectives lower to ``<kind>-start`` / ``<kind>-done``
    pairs; both map onto the base kind so callers can classify an op
    exactly once instead of re-deriving the suffix logic (the source
    of the double-count this module used to have).  Non-collective
    ops are returned unchanged.
    """
    for kind in COLLECTIVES:
        if op == kind or (op.startswith(kind)
                          and op[len(kind):] in ("-start", "-done")):
            return kind
    return op

# one scalar/array shape like  bf16[8,128]{1,0:T(8,128)}  or  f32[]
_SHAPE_RE = re.compile(
    r"([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?")


@dataclasses.dataclass
class Shape:
    dtype: str
    dims: Tuple[int, ...]

    @property
    def elems(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def bytes(self) -> int:
        bs = _DTYPE_BYTES.get(self.dtype)
        if bs is None:
            if self.dtype not in _WARNED_DTYPES:
                _WARNED_DTYPES.add(self.dtype)
                warnings.warn(
                    f"hlo_cost: unknown HLO dtype {self.dtype!r}; "
                    "treating as 0 bytes — add it to _DTYPE_BYTES "
                    "so roofline terms stay exact", stacklevel=2)
            return 0
        return self.elems * bs


# dtypes already warned about (once per process, not once per shape)
_WARNED_DTYPES: set = set()


@dataclasses.dataclass
class Instr:
    name: str
    shapes: List[Shape]          # >1 for tuple results
    op: str
    operands: List[str]
    attrs: str                   # raw trailing text
    operand_txt: str = ""        # raw text inside the op's parens

    @property
    def bytes(self) -> int:
        return sum(s.bytes for s in self.shapes)

    @property
    def const_val(self) -> Optional[int]:
        if self.op != "constant":
            return None
        m = re.match(r"\s*(-?\d+)\s*$", self.operand_txt)
        return int(m.group(1)) if m else None


def _parse_shapes(text: str) -> List[Shape]:
    return [Shape(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE_RE.findall(text)]


_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMMENT_RE = re.compile(r"/\*[^*]*\*/")


def _split_instr(rest: str):
    """rest after '<name> = ' -> (shape_txt, op, operand_txt, attrs)."""
    rest = rest.lstrip()
    if rest.startswith("("):          # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        shape_txt, rest = rest[:i + 1], rest[i + 1:]
    else:                              # single shape token
        sp = rest.find(" ")
        if sp < 0:
            return None
        shape_txt, rest = rest[:sp], rest[sp:]
    m = re.match(r"\s*([\w\-]+)\((.*)$", rest)
    if not m:
        return None
    op, rest = m.groups()
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
    return shape_txt, op, rest[:i], rest[i + 1:]


def parse_module(text: str) -> Dict[str, List[Instr]]:
    """{computation name: [Instr]}; entry computation under 'ENTRY'."""
    comps: Dict[str, List[Instr]] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        s = _COMMENT_RE.sub("", line.rstrip())
        if not s:
            continue
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*{", s)
        if m and not s.lstrip().startswith("ROOT") and "= " not in s:
            cur = "ENTRY" if m.group(1) else m.group(2)
            comps[cur] = []
            continue
        if cur is None:
            continue
        mn = _NAME_RE.match(s)
        if not mn:
            continue
        name, rest = mn.groups()
        parts = _split_instr(rest)
        if parts is None:
            continue
        shape_txt, op, operand_txt, attrs = parts
        operands = re.findall(r"%([\w.\-]+)", operand_txt)
        comps[cur].append(Instr(name, _parse_shapes(shape_txt), op,
                                operands, attrs, operand_txt))
    return comps


def _called(attrs: str, key: str) -> Optional[str]:
    m = re.search(key + r"=%?([\w.\-]+)", attrs)
    return m.group(1) if m else None


def _trip_count(cond: List[Instr]) -> Optional[int]:
    """Recover the scan trip count from the loop condition.

    ``lax.scan`` lowers to a 0-start, +1-step counter compared (LT)
    against a scalar integer constant that lives in the condition
    computation (possibly behind a kLoop compare fusion).  We take the
    largest plausible scalar int constant in the condition as the trip
    count — exact for scan/fori loops, and recorded as 1 when no such
    constant exists (dynamic-bound loops).
    """
    best = None
    for i in cond:
        v = i.const_val
        if v is not None and i.shapes and not i.shapes[0].dims \
                and i.shapes[0].dtype in ("s32", "u32", "s64", "u64") \
                and 0 < v < 10_000_000:
            best = v if best is None else max(best, v)
    return best


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    bytes_tpu: float = 0.0
    coll: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVES})

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += mult * other.flops
        self.bytes += mult * other.bytes
        self.bytes_tpu += mult * other.bytes_tpu
        for k in COLLECTIVES:
            self.coll[k] += mult * other.coll[k]


_SKIP_BYTES = {"parameter", "get-tuple-element", "tuple", "constant",
               "bitcast", "copy-start", "copy-done", "after-all",
               "partition-id", "replica-id", "iota"}

# TPU-fusion byte model: ops that MATERIALIZE HBM traffic on a TPU.
# XLA:TPU fuses elementwise/broadcast/reduce chains into their
# producers, so on real hardware only these round-trip HBM: matmul
# operands/results, data-movement ops (gather/scatter/slice-updates,
# copies), RNG, and decompositions.  Elementwise chains (residual
# adds, norms, optimizer update) ride along with entry
# parameters/outputs, which the dry-run adds separately
# (``memory_analysis().argument/output``).  This is the same
# convention as analytic transformer rooflines; the raw per-op count
# (``bytes``) is kept as the CPU-fusion-granularity upper bound.
# Not included: ``copy`` (dot-operand transposes — TPU dot_general
# contracts arbitrary dims, layout assignment absorbs the rest) and
# ``reduce-window`` (XLA:CPU's blocked lowering of softmax reductions;
# an input-fused reduce on TPU).
_MATERIALIZE = {"dot", "convolution", "gather", "scatter",
                "dynamic-slice", "dynamic-update-slice", "sort",
                "custom-call", "rng-bit-generator", "cholesky",
                "triangular-solve", "fft",
                "select-and-scatter", "pad", "concatenate"}


class HloCost:
    def __init__(self, text: str):
        self.comps = parse_module(text)
        # constants values for trip counts
        self._cache: Dict[Tuple[str, bool], Cost] = {}

    # -- per-instruction helpers ------------------------------------

    def _dot_flops(self, ins: Instr, table: Dict[str, Instr]) -> float:
        out = ins.shapes[0]
        lhs = table.get(ins.operands[0]) if ins.operands else None
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.attrs)
        contract = 1
        if lhs is not None and m and lhs.shapes:
            for d in m.group(1).split(","):
                if d:
                    contract *= lhs.shapes[0].dims[int(d)]
        return 2.0 * out.elems * contract

    def _conv_flops(self, ins: Instr, table: Dict[str, Instr]) -> float:
        out = ins.shapes[0]
        rhs = table.get(ins.operands[1]) if len(ins.operands) > 1 else None
        if rhs is None or not rhs.shapes:
            return 0.0
        # 2 * output elems * (kernel elems / output-feature dim):
        # correct for dense convs, conservative for grouped/depthwise
        k = rhs.shapes[0]
        out_feat = max(k.dims) if k.dims else 1
        return 2.0 * out.elems * max(1, k.elems // out_feat)

    # -- recursive evaluation ----------------------------------------

    def cost_of(self, comp: str, in_fusion: bool = False) -> Cost:
        key = (comp, in_fusion)
        if key in self._cache:
            return self._cache[key]
        total = Cost()
        self._cache[key] = total  # guards recursion
        instrs = self.comps.get(comp, [])
        table = {i.name: i for i in instrs}
        for ins in instrs:
            kind = op_kind(ins.op)
            is_coll = kind in COLLECTIVES
            is_done = is_coll and ins.op.endswith("-done")
            if ins.op == "dot":
                total.flops += self._dot_flops(ins, table)
            elif ins.op == "convolution":
                total.flops += self._conv_flops(ins, table)
            elif is_coll and not is_done:
                # An async pair (-start/-done) is ONE transfer: all
                # accounting happens on the -start op (its tuple result
                # aliases operand+result, so subtract operand bytes to
                # recover the result payload); -done is pure bookkeeping
                # and contributes nothing.
                result_b = ins.bytes
                if ins.op.endswith("-start") and len(ins.shapes) > 1:
                    result_b = max(0, ins.bytes - sum(
                        table[o].bytes for o in ins.operands
                        if o in table))
                # per-chip ICI wire bytes (ring algorithms, (N-1)/N ~ 1):
                #   all-gather        ~ result bytes (receives the world)
                #   all-reduce        ~ 2x payload (reduce + broadcast)
                #   reduce-scatter    ~ operand bytes
                #   all-to-all / cp / broadcast ~ operand bytes
                opb = sum(table[o].bytes for o in ins.operands
                          if o in table)
                if opb == 0:
                    opb = result_b
                if kind == "all-gather":
                    b = max(result_b, opb)
                elif kind == "all-reduce":
                    b = 2 * opb
                else:
                    b = opb
                total.coll[kind] += b
                if not in_fusion:
                    total.bytes += result_b

            if ins.op == "while":
                body = _called(ins.attrs, "body")
                cond = _called(ins.attrs, "condition")
                # XLA prints the derived trip count in backend_config
                mt = re.search(r'known_trip_count[^}]*?"n"\s*:\s*"(\d+)"',
                               ins.attrs)
                trip = int(mt.group(1)) if mt else None
                if trip is None and cond and cond in self.comps:
                    trip = _trip_count(self.comps[cond])
                trip = trip if trip else 1
                if body:
                    total.add(self.cost_of(body, in_fusion), trip)
                if cond:
                    total.add(self.cost_of(cond, in_fusion), trip)
                continue
            if ins.op in ("fusion",):
                callee = _called(ins.attrs, "calls")
                if callee:
                    total.add(self.cost_of(callee, True))
            elif ins.op in ("call", "async-start"):
                callee = _called(ins.attrs, "calls") or \
                    _called(ins.attrs, "to_apply")
                if callee:
                    total.add(self.cost_of(callee, in_fusion))
            elif ins.op == "conditional":
                for key2 in ("true_computation", "false_computation"):
                    callee = _called(ins.attrs, key2)
                    if callee:
                        total.add(self.cost_of(callee, in_fusion))

            # HBM traffic: top-level (non-fusion-body) instructions
            # (collectives — sync, -start AND -done — are fully
            # accounted in the collective branch above)
            if not in_fusion and ins.op not in _SKIP_BYTES \
                    and not is_coll:
                b = ins.bytes
                for o in ins.operands:
                    if o in table and table[o].op not in (
                            "tuple", "constant"):
                        b += table[o].bytes
                total.bytes += b
            # TPU-fusion model: materialization points only, counted
            # whether or not CPU-XLA happened to fuse them
            if ins.op in _MATERIALIZE and not is_coll:
                if ins.op in ("dynamic-slice", "gather"):
                    # reads only the sliced/gathered elements
                    b = 2 * ins.bytes
                elif ins.op == "dynamic-update-slice":
                    # in-place: read the update operand, write the slice
                    upd = (table.get(ins.operands[1])
                           if len(ins.operands) > 1 else None)
                    b = 2 * (upd.bytes if upd is not None else ins.bytes)
                elif ins.op == "scatter":
                    upd = (table.get(ins.operands[2])
                           if len(ins.operands) > 2 else None)
                    b = 2 * (upd.bytes if upd is not None else ins.bytes)
                else:
                    b = ins.bytes
                    for o in ins.operands:
                        if o in table and table[o].op not in (
                                "tuple", "constant"):
                            b += table[o].bytes
                total.bytes_tpu += b
            elif is_coll and not is_done:
                b = ins.bytes
                if ins.op.endswith("-start") and len(ins.shapes) > 1:
                    b = max(0, ins.bytes - sum(
                        table[o].bytes for o in ins.operands
                        if o in table))
                total.bytes_tpu += b
        return total

    def entry_cost(self) -> Cost:
        entry = "ENTRY" if "ENTRY" in self.comps else \
            next(iter(self.comps))
        return self.cost_of(entry)


def analyze(text: str) -> Dict[str, float]:
    """Trip-count-aware {flops, bytes, collective bytes by kind}.

    ``bytes_accessed``     — every top-level op (CPU-fusion upper bound)
    ``bytes_materialized`` — TPU-fusion model (see _MATERIALIZE); add
                             entry argument/output bytes for the total.
    """
    c = HloCost(text).entry_cost()
    coll = dict(c.coll)
    coll["total"] = sum(coll.values())
    return {"flops": c.flops, "bytes_accessed": c.bytes,
            "bytes_materialized": c.bytes_tpu,
            "collective_bytes": coll}
