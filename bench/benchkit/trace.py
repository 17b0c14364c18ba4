"""Reduce a profiler trace (``.xplane.pb``) to per-layer numbers.

What it reads, per device: the ops on the device's "XLA Ops" line
(each event's name is the HLO instruction, its duration the device
time) and the programs on the "XLA Modules" line; on the host, the
benchmark's own ``TraceAnnotation`` spans.  A CPU trace has no device
plane; there the XLA ops of the CPU client's threads stand in, so the
same code runs in the CPU rehearsal.

An op's stage comes from, in order: its opcode (collectives are the
``exchange``), its program's name (a stage may claim whole programs),
then the program function that holds the op's metadata: the compiled
HLO text lists every instruction's ``stack_frame_id`` and the stack
frame tables, so the instruction resolves to the chain of Python
functions that emitted it, innermost first, and the first one a stage
claims wins.  Ops no stage claims are ``other``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|collective-permute|"
                        r"reduce-scatter|all-to-all|collective-broadcast)")
_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_MODULE = re.compile(r"^(.*?)\(\d+\)$")


class Op(NamedTuple):
    start: int          # ns
    end: int            # ns
    name: str           # HLO instruction name
    module: str         # program name, without its fingerprint


class Span(NamedTuple):
    start: int
    end: int
    name: str


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _module_name(event_name: str) -> str:
    m = _MODULE.match(event_name)
    return m.group(1) if m else event_name


def _instr_name(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def load(path: str):
    """(ops per device, the host's Python-thread spans) from one trace
    file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    cpu_ops: List[Op] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns),
                 _module_name(e.name))
                for e in (lines["XLA Modules"].events
                          if "XLA Modules" in lines else []))
            ops = []
            mi = 0
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines
                      else []):
                s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
                while mi + 1 < len(mods) and mods[mi + 1][0] <= s:
                    mi += 1
                mod = mods[mi][2] if mods and mods[mi][0] <= s else ""
                ops.append(Op(s, t, _instr_name(e.name), mod))
            devices[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for e in ln.events:
                    s = int(e.start_ns)
                    t = int(e.start_ns + e.duration_ns)
                    stats = dict(e.stats)
                    if "hlo_op" in stats and "hlo_module" in stats \
                            and e.duration_ns > 0:
                        cpu_ops.append(Op(s, t, str(stats["hlo_op"]),
                                          str(stats["hlo_module"])))
                    elif ln.name.startswith("python"):
                        spans.append(Span(s, t, e.name))
    if not devices and cpu_ops:
        devices["/host:CPU"] = sorted(cpu_ops)
    return devices, sorted(spans)


# -- compiled HLO: instruction -> Python function chain ---------------------

_TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$")


def hlo_frames(text: str) -> Dict[str, Tuple[str, ...]]:
    """Instruction name -> function names that emitted it, innermost
    first, from a compiled module's text.

    An instruction that carries no stack frame (the TPU's Cholesky and
    triangular-inverse custom calls carry only an op name) takes the
    frames of its first operand that has them: the function that built
    its input."""
    funcs: Dict[int, str] = {}
    locs: Dict[int, int] = {}
    frames: Dict[int, Tuple[int, int]] = {}
    table = None
    instr_frame: Dict[str, int] = {}
    operands: Dict[str, List[str]] = {}
    for line in text.splitlines():
        s = line.strip()
        if _TABLE.match(s):
            table = s
            continue
        if table is not None:
            m = re.match(r'^(\d+) (.*)$', s)
            if not m:
                table = None
            else:
                k, rest = int(m.group(1)), m.group(2)
                if table == "FunctionNames":
                    funcs[k] = rest.strip('"')
                elif table == "FileLocations":
                    f = re.search(r"function_name_id=(\d+)", rest)
                    locs[k] = int(f.group(1)) if f else 0
                elif table == "StackFrames":
                    a = re.search(r"file_location_id=(\d+)", rest)
                    b = re.search(r"parent_frame_id=(\d+)", rest)
                    frames[k] = (int(a.group(1)) if a else 0,
                                 int(b.group(1)) if b else 0)
                continue
        m = _INSTR.match(s.replace("ROOT ", "", 1))
        if m:
            f = re.search(r"stack_frame_id=(\d+)", s)
            if f:
                instr_frame[m.group(1)] = int(f.group(1))
            else:
                operands[m.group(1)] = re.findall(
                    r"%([\w.\-]+)", s.split("metadata=")[0])[1:]
    out = {}
    for name, fid in instr_frame.items():
        chain, seen = [], set()
        while fid and fid not in seen:
            seen.add(fid)
            loc, parent = frames.get(fid, (0, 0))
            fn = funcs.get(locs.get(loc, 0))
            if fn:
                chain.append(fn)
            fid = parent
        out[name] = tuple(chain)
    for name, ops in operands.items():
        for op in ops:
            if out.get(op):
                out[name] = out[op]
                break
    return out


class Stages:
    """Stage tables: ``{stage: {"modules": [...], "functions": [...]}}``."""

    def __init__(self, tables: Dict[str, dict],
                 hlo: Optional[Dict[str, str]] = None):
        self.modules = {m: st for st, t in tables.items()
                        for m in t.get("modules", ())}
        self.functions = {f: st for st, t in tables.items()
                          for f in t.get("functions", ())}
        self.frames = {mod: hlo_frames(txt) for mod, txt in
                       (hlo or {}).items()}

    def of(self, op: Op) -> str:
        if COLLECTIVE.match(op.name):
            return "exchange"
        if op.module in self.modules:
            return self.modules[op.module]
        for fn in self.frames.get(op.module, {}).get(op.name, ()):
            if fn in self.functions:
                return self.functions[fn]
        return "other"


# -- interval arithmetic ----------------------------------------------------

def _union(iv):
    out = []
    for s, t in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(t, hi)) for s, t in iv if t > lo and s < hi]


def _length(iv) -> int:
    return sum(t - s for s, t in iv)


def _minus(a, b):
    """Measure of union(a) minus union(b)."""
    a, b = _union(a), _union(b)
    total, j = 0, 0
    for s, t in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < t:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < t:
            total += t - cur
    return total


class Reduced(NamedTuple):
    window_s: float
    busy_s: Dict[str, float]           # per device
    stage_s: Dict[str, Dict[str, float]]   # device -> stage -> seconds
    exposed_s: Dict[str, float]        # per device: collective, no compute
    gaps: List[Tuple[str, float]]      # idle seconds by host activity


def reduce(path: str, window: Tuple[int, int], stages: Stages,
           spans: Optional[List[Span]] = None) -> Reduced:
    """Reduce the trace at ``path`` over ``window`` (host ns)."""
    devices, host = load(path)
    lo, hi = window
    busy, stage_s, exposed = {}, {}, {}
    gap_names: Dict[str, float] = defaultdict(float)
    host = spans if spans is not None else host
    for dev, ops in devices.items():
        ops = [o for o in ops if o.end > lo and o.start < hi]
        iv = _union(_clip([(o.start, o.end) for o in ops], lo, hi))
        busy[dev] = _length(iv) * 1e-9
        # a loop op's event spans its body's ops, which the line lists
        # too: a stage's time is the union of its ops' intervals
        per = defaultdict(list)
        coll, comp = [], []
        for o in ops:
            st = stages.of(o)
            s, t = max(o.start, lo), min(o.end, hi)
            per[st].append((s, t))
            (coll if st == "exchange" else comp).append((s, t))
        stage_s[dev] = {st: _length(_union(iv)) * 1e-9
                        for st, iv in per.items()}
        exposed[dev] = _minus(coll, comp) * 1e-9
        # idle gaps, named by the innermost host span open at their middle
        mids, prev = [], lo
        for s_, t in iv + [(hi, hi)]:
            if s_ > prev:
                mids.append(((s_ + prev) // 2, (s_ - prev) * 1e-9))
            prev = max(prev, t)
        for (mid, length), name in zip(mids, _open_spans(
                host, [m for m, _ in mids])):
            gap_names[name] += length
    return Reduced((hi - lo) * 1e-9, busy, stage_s, exposed,
                   sorted(gap_names.items(), key=lambda kv: -kv[1]))


def _open_spans(spans: List[Span], times: List[int]) -> List[str]:
    """For each of the increasing ``times``, the innermost span of one
    thread's properly nested ``spans`` open then."""
    out, stack, i = [], [], 0
    spans = sorted(spans)
    for t in times:
        while i < len(spans) and spans[i].start <= t:
            while stack and stack[-1].end < spans[i].start:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1].end < t:
            stack.pop()
        out.append(stack[-1].name if stack else "(no host span)")
    return out


def bench_window(spans: List[Span], name: str) -> Tuple[int, int]:
    """The host interval of the span ``name`` (the measured window)."""
    for sp in spans:
        if sp.name == name:
            return sp.start, sp.end
    raise ValueError(f"no host span {name!r} in the trace")
