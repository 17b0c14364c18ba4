"""The program's own spans in a profiler trace, and the device's idle
time under them.

The program (``repro.obs``) enters a ``jax.profiler.TraceAnnotation``
for each of its spans, so in a trace they sit on the host plane's
Python thread lines beside JAX's own dispatch spans, on the device
trace's clock.  Each xplane line is one thread, and spans are kept per
line: the sample writer's ``ckpt/save`` overlaps the loop thread's
spans and must not be nested into them.

Only the program's spans (``is_program``) name time here; JAX's spans
(``PjitFunction(...)``, ``np.asarray(...)``) and the benchmark's
(``bench.*``) do not.  Each idle nanosecond of a device is named by the
innermost program span open on the thread that holds the window.
``trace.reduce``'s ``idle_gaps`` stays as it was: it names a gap by the
innermost span of any kind, on any thread.
"""
from __future__ import annotations

from bisect import bisect_right
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

from . import trace
from .trace import Span

LAYERS = ("session/", "ckpt/", "serve/", "predict/")


def is_program(name: str) -> bool:
    """A span of the program: a layer's ``<layer>/<phase>``, the
    session's ``sweep`` or a garbage collection's ``gc``."""
    return name in ("sweep", "gc") or name.startswith(LAYERS)


def load_threads(path: str) -> List[List[Span]]:
    """The host's Python-thread spans of one trace file, one sorted
    list per thread (xplane line)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for ln in plane.lines:
            if ln.name.startswith("python"):
                out.append(sorted(
                    Span(int(e.start_ns), int(e.start_ns + e.duration_ns),
                         e.name) for e in ln.events))
    return out


def leaves(spans: List[Span]) -> List[Tuple[int, int, str]]:
    """Disjoint ``(start, end, name)`` pieces of one thread's nested
    spans, each named by the innermost span open over it."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Span] = []
    pos = 0

    def emit(a: int, b: int, name: str) -> None:
        if b > a:
            out.append((a, b, name))

    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= sp.start:
            top = stack.pop()
            emit(pos, top.end, top.name)
            pos = max(pos, top.end)
        if stack:
            emit(pos, sp.start, stack[-1].name)
        stack.append(sp)
        pos = sp.start
    while stack:
        top = stack.pop()
        emit(pos, top.end, top.name)
        pos = max(pos, top.end)
    return out


class Covered:
    """Length of any interval covered by fixed intervals, by bisection
    over their union."""

    def __init__(self, iv):
        self.iv = trace._union(iv)
        self.starts = [s for s, _ in self.iv]
        self.cum = [0]
        for s, t in self.iv:
            self.cum.append(self.cum[-1] + t - s)

    def _upto(self, x: int) -> int:
        i = bisect_right(self.starts, x) - 1
        if i < 0:
            return 0
        s, t = self.iv[i]
        return self.cum[i] + min(x, t) - s

    def __call__(self, a: int, b: int) -> int:
        return self._upto(b) - self._upto(a) if b > a else 0


class Program:
    """The program's spans on the thread that holds the benchmark's
    window, and each device's busy time, over that window.

    ``threads``: each host thread's spans; ``devices``: each device's
    op intervals (ns)."""

    def __init__(self, threads: List[List[Span]],
                 devices: Dict[str, List[Tuple[int, int]]],
                 window: str = "bench.window"):
        main = next((t for t in threads
                     if any(s.name == window for s in t)), [])
        win = [s for s in main if s.name == window]
        self.lo, self.hi = (win[0].start, win[0].end) if win else (0, 0)
        self.spans = [s for s in main if is_program(s.name)
                      and s.end > self.lo and s.start < self.hi]
        self.busy = {dev: Covered(trace._clip(iv, self.lo, self.hi))
                     for dev, iv in devices.items()}

    @classmethod
    def read(cls, path: str) -> "Program":
        devices, _ = trace.load(path)
        return cls(load_threads(path),
                   {dev: [(o.start, o.end) for o in ops]
                    for dev, ops in devices.items()})

    def count(self, name: str) -> int:
        """Spans named ``name`` that start inside the window."""
        return sum(1 for s in self.spans
                   if s.name == name and s.start >= self.lo)

    def duration(self, names) -> float:
        """Seconds inside the window under the union of the spans
        named in ``names``."""
        iv = trace._union(trace._clip(
            [(s.start, s.end) for s in self.spans if s.name in names],
            self.lo, self.hi))
        return trace._length(iv) * 1e-9

    def idle(self, top: Callable[[str], bool]
             ) -> Optional[Tuple[float, Dict[str, float]]]:
        """Device-idle seconds while the thread is inside a span that
        ``top`` selects, mean over devices, in all and by the innermost
        program span; None where no such span or no device op is
        there."""
        region = trace._union(trace._clip(
            [(s.start, s.end) for s in self.spans if top(s.name)],
            self.lo, self.hi))
        if not region or not self.busy:
            return None
        inside = Covered(region)
        pieces = [(max(a, self.lo), min(b, self.hi), n)
                  for a, b, n in leaves(self.spans)]
        by: Dict[str, float] = defaultdict(float)
        total = 0.0
        for busy in self.busy.values():
            for a, b, name in pieces:
                if b <= a:
                    continue
                # the region is a union of whole spans, so a piece lies
                # wholly inside it or outside it
                if inside(a, b) < b - a:
                    continue
                ns = (b - a) - busy(a, b)
                by[name] += ns * 1e-9 / len(self.busy)
                total += ns * 1e-9 / len(self.busy)
        return total, dict(by)

    def window_idle_s(self) -> float:
        """Device-idle seconds of the whole window, mean over devices."""
        n = self.hi - self.lo
        return sum(n - b(self.lo, self.hi) for b in self.busy.values()) \
            * 1e-9 / max(len(self.busy), 1)


def of(run) -> Program:
    """The run's ``Program``, read from its trace once."""
    prog = getattr(run, "_program_spans", None)
    if prog is None:
        prog = Program.read(trace.find_xplane(run._trace_dir))
        run._program_spans = prog
    return prog
