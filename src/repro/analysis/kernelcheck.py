"""Static Pallas kernel contract verifier.

The CPU container only ever runs the kernels in ``kernels/`` in
interpret mode, so grid races, out-of-bounds index maps, and VMEM
overflows would surface for the first time on real TPU hardware.  This
pass closes that gap **without a TPU**: it discovers every
``pl.pallas_call`` site through the :data:`repro.kernels.ops.KERNELS`
registry, concretely enumerates each kernel's grid over its shipped
block-size configurations (the registry probes), and checks four
contracts per kernel:

``kernel-output-race``
    Every output block index is produced by exactly one grid point,
    or — for revisit-accumulate patterns (e.g. ``gram``'s
    ``(r, 0, 0)`` output revisited across ``t``) — the revisited
    output/scratch is provably initialized at the first visit
    (``@pl.when(t == 0)`` guard detected from the kernel AST) before
    any read-modify-write.
``kernel-block-out-of-bounds``
    Every input/output index map stays inside the padded operand
    shape for ALL grid points, uneven tails included — because probes
    drive the public wrappers, the shared ``ops.pad_to_blocks``
    arithmetic is verified as part of the same enumeration.
``kernel-accum-dtype``
    Contractions carry ``preferred_element_type=jnp.float32`` and
    every across-grid accumulator (output or scratch) is fp32 — the
    contract ``topk_score`` and ``gram`` honor so bf16/fp16 operands
    never accumulate in low precision.
``kernel-vmem-budget``
    Per-grid-step resident bytes (double-buffered block tiles +
    scratch) estimated and bounded against the registry's per-kernel
    budget.  :func:`vmem_report` records the estimate into every
    ``results/dryrun/*.json`` (audited by
    ``contract.dryrun_contract_findings``).

How capture works
-----------------
Unlike :mod:`.invariants` (pure AST, never imports), this pass *does*
import the kernel modules: it monkey-patches
``jax.experimental.pallas.pallas_call`` with a recording shim and
traces each probe with ``jax.eval_shape`` — so the grids, BlockSpecs,
index maps, scratch shapes, and padded operand shapes it checks are
exactly the shipped ones, with zero re-declaration drift.  The guard
analysis (``@pl.when``) and dtype checks then run on the kernel
function's AST.  Jitted entry points are cache-cleared around the
capture (a cached real trace would skip ``pallas_call``; a cached
capture trace would poison later real calls).

Findings use the PR 6 format (file:line, rule id, fix hint) and honor
``# repro-lint: disable=<rule>`` suppressions.  Fixture files under
``tests/fixtures/analysis/kernel_bad_*.py`` carry their own
``KERNELS`` registry and are checked via :func:`check_kernel_paths`.
"""
from __future__ import annotations

import ast
import dataclasses
import functools
import importlib.util
import inspect
import itertools
import math
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from . import invariants
from .invariants import Finding

# rule ids; registered into invariants.RULES below so --list-rules /
# --rules compose (their per-file lint check is a no-op — the real
# logic needs the registry and runs through check_kernels)
RACE = "kernel-output-race"
BOUNDS = "kernel-block-out-of-bounds"
DTYPE = "kernel-accum-dtype"
VMEM = "kernel-vmem-budget"
KERNEL_RULE_IDS = (RACE, BOUNDS, DTYPE, VMEM)


def _noop_rule(ctx):
    return ()


invariants.rule(
    RACE,
    "every Pallas output block is written by exactly one grid point, "
    "or revisit-accumulate with a @pl.when(t == 0) first-visit init",
    "PR 8: the CPU container never executes the compiled grid, so an "
    "uninitialized revisited accumulator or a doubly-written block "
    "would surface for the first time on real TPU hardware",
)(_noop_rule)
invariants.rule(
    BOUNDS,
    "every Pallas index map stays inside the padded operand shape for "
    "all grid points (uneven tails included)",
    "PR 8: block-index arithmetic against ops.pad_to_blocks padding "
    "is enumerated concretely — an off-by-one tail reads garbage (or "
    "faults) only on hardware",
)(_noop_rule)
invariants.rule(
    DTYPE,
    "kernel contractions carry preferred_element_type=jnp.float32 and "
    "across-grid accumulators are fp32",
    "PR 8: bf16 operands must accumulate in fp32 (the contract gram/"
    "topk_score honor); a bf16 accumulator loses the posterior mean "
    "at catalogue scale",
)(_noop_rule)
invariants.rule(
    VMEM,
    "per-grid-step resident bytes (double-buffered block tiles + "
    "scratch) stay under the kernel's registry VMEM budget",
    "PR 8: ~16 MB of VMEM per core; an over-budget block config "
    "compiles fine in interpret mode and OOMs only on the TPU",
)(_noop_rule)


# ---------------------------------------------------------------------------
# capture: record every pl.pallas_call a probe trace reaches
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PallasCapture:
    """One recorded ``pl.pallas_call`` site, fully concrete."""
    kernel_name: str
    src_path: str                  # file defining the kernel function
    grid: Tuple[int, ...]
    in_specs: List[Any]
    out_specs: List[Any]
    out_shapes: List[Any]          # ShapeDtypeStructs
    operands: Tuple[Any, ...]      # padded ShapeDtypeStructs
    scratch: List[Tuple[Tuple[int, ...], Any]]   # (shape, dtype)
    probe_label: str = ""


def _as_list(x) -> List[Any]:
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _unwrap_kernel(kernel):
    while isinstance(kernel, functools.partial):
        kernel = kernel.func
    return kernel


def _clear(fns) -> None:
    for fn in fns:
        clear = getattr(fn, "clear_cache", None)
        if callable(clear):
            clear()


def capture_probe(probe) -> List[PallasCapture]:
    """Trace one registry probe under a recording ``pallas_call`` shim
    and return every call site it reached."""
    import jax
    import jax.numpy as jnp
    # the capture works by patching the very module the kernels call
    # into — this is the one justified use outside compat.py/kernels/
    import jax.experimental.pallas as plmod  # repro-lint: disable=experimental-import-outside-compat

    caps: List[PallasCapture] = []
    orig = plmod.pallas_call

    def shim(kernel, **kw):
        kfn = _unwrap_kernel(kernel)
        scratch = []
        for s in kw.get("scratch_shapes") or ():
            scratch.append((tuple(getattr(s, "shape", ())),
                            jnp.dtype(getattr(s, "dtype", jnp.float32))))
        cap = PallasCapture(
            kernel_name=kfn.__name__,
            src_path=inspect.getsourcefile(kfn) or "<unknown>",
            grid=tuple(int(g) for g in _as_list(kw.get("grid"))),
            in_specs=_as_list(kw.get("in_specs")),
            out_specs=_as_list(kw.get("out_specs")),
            out_shapes=_as_list(kw.get("out_shape")),
            operands=(), scratch=scratch,
            probe_label=probe.label)
        caps.append(cap)
        single_out = not isinstance(kw.get("out_shape"), (list, tuple))

        def run(*operands):
            cap.operands = tuple(
                jax.ShapeDtypeStruct(jnp.shape(o), o.dtype)
                for o in operands)
            outs = [jnp.zeros(s.shape, s.dtype) for s in cap.out_shapes]
            return outs[0] if single_out else type(
                kw["out_shape"])(outs)

        return run

    plmod.pallas_call = shim
    try:
        # trace through a FRESH wrapper: jax.eval_shape keys its trace
        # cache on the function object, so re-tracing probe.call
        # directly would silently hit a cached trace and skip the shim
        jax.eval_shape(lambda *a: probe.call(*a), *probe.args)
    finally:
        plmod.pallas_call = orig
    return caps


def capture_spec(spec) -> List[PallasCapture]:
    """All captures for one registry entry (cache-cleared around each
    probe so stale jit traces neither skip nor poison the capture)."""
    caps: List[PallasCapture] = []
    for probe in spec.probes:
        _clear(spec.jit_fns)
        try:
            caps.extend(capture_probe(probe))
        finally:
            _clear(spec.jit_fns)
    return caps


# ---------------------------------------------------------------------------
# kernel-function AST analysis: program ids, guarded writes/reads, dots
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Access:
    line: int
    kind: str            # "assign" | "aug" | "read"
    guard: Tuple[str, Optional[int]]   # (class, grid axis) — class in
    #                     {"eq0","ne0","eq","other","none"}


class KernelAst:
    """Guard-aware access analysis of one kernel function."""

    def __init__(self, path: str, fn_name: str):
        self.path = path
        src = Path(path).read_text()
        self.fn: Optional[ast.FunctionDef] = None
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.FunctionDef) and \
                    node.name == fn_name:
                self.fn = node
                break
        self.params: List[str] = []
        self.pid_axes: Dict[str, int] = {}     # var -> grid axis
        self.access: Dict[str, List[_Access]] = {}
        self.dots: List[Tuple[int, bool]] = []  # (line, has f32 pref)
        self.pallas_line = 1
        if self.fn is None:
            return
        a = self.fn.args
        self.params = [p.arg for p in a.posonlyargs + a.args]
        self._when_calls = self._collect_when_calls(self.fn)
        self._collect_program_ids(self.fn)
        self._walk(self.fn, ("none", None))

    # -- collection helpers -------------------------------------------------

    @staticmethod
    def _is_when(call: ast.AST) -> Optional[ast.expr]:
        """``pl.when(cond)`` (or bare ``when(cond)``) -> cond."""
        if not (isinstance(call, ast.Call) and call.args):
            return None
        f = call.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else "")
        return call.args[0] if name == "when" else None

    def _collect_when_calls(self, fn) -> Dict[str, ast.expr]:
        """``pl.when(cond)(inner)`` call-style guards: name -> cond."""
        out: Dict[str, ast.expr] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and len(node.args) == 1 and \
                    isinstance(node.args[0], ast.Name):
                cond = self._is_when(node.func)
                if cond is not None:
                    out[node.args[0].id] = cond
        return out

    def _collect_program_ids(self, fn) -> None:
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)):
                continue
            f = node.value.func
            name = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else "")
            if name == "program_id" and node.value.args and \
                    isinstance(node.value.args[0], ast.Constant):
                self.pid_axes[node.targets[0].id] = \
                    int(node.value.args[0].value)

    def _classify(self, cond: ast.expr) -> Tuple[str, Optional[int]]:
        """Map a ``pl.when`` condition onto (class, grid axis)."""
        involved = sorted({self.pid_axes[n.id]
                           for n in ast.walk(cond)
                           if isinstance(n, ast.Name)
                           and n.id in self.pid_axes})
        axis = involved[-1] if involved else None
        if isinstance(cond, ast.Compare) and len(cond.ops) == 1:
            lhs, rhs = cond.left, cond.comparators[0]
            var, other = (lhs, rhs) if isinstance(lhs, ast.Name) \
                else (rhs, lhs)
            if isinstance(var, ast.Name) and var.id in self.pid_axes:
                ax = self.pid_axes[var.id]
                zero = isinstance(other, ast.Constant) and \
                    other.value == 0
                if isinstance(cond.ops[0], ast.Eq):
                    return ("eq0" if zero else "eq"), ax
                if isinstance(cond.ops[0], ast.NotEq) and zero:
                    return "ne0", ax
        return ("other" if axis is not None else "none"), axis

    # -- guarded walk -------------------------------------------------------

    def _walk(self, node: ast.AST, guard) -> None:
        for child in ast.iter_child_nodes(node):
            g = guard
            if isinstance(child, ast.FunctionDef) and child is not self.fn:
                cond = None
                for deco in child.decorator_list:
                    cond = self._is_when(deco)
                    if cond is not None:
                        break
                if cond is None:
                    cond = self._when_calls.get(child.name)
                g = self._classify(cond) if cond is not None else guard
            self._record(child, g)
            self._walk(child, g)

    def _record(self, node: ast.AST, guard) -> None:
        def ref_of(target) -> Optional[str]:
            if isinstance(target, ast.Subscript) and \
                    isinstance(target.value, ast.Name) and \
                    target.value.id in self.params:
                return target.value.id
            return None

        if isinstance(node, ast.Assign):
            for t in node.targets:
                name = ref_of(t)
                if name:
                    self.access.setdefault(name, []).append(
                        _Access(node.lineno, "assign", guard))
        elif isinstance(node, ast.AugAssign):
            name = ref_of(node.target)
            if name:
                self.access.setdefault(name, []).append(
                    _Access(node.lineno, "aug", guard))
        elif isinstance(node, ast.Subscript) and \
                isinstance(node.ctx, ast.Load):
            if isinstance(node.value, ast.Name) and \
                    node.value.id in self.params:
                self.access.setdefault(node.value.id, []).append(
                    _Access(node.lineno, "read", guard))
        elif isinstance(node, ast.Call):
            f = node.func
            name = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else "")
            if name in ("dot_general", "einsum", "dot", "matmul"):
                pref = any(
                    kw.arg == "preferred_element_type"
                    and "float32" in ast.unparse(kw.value)
                    for kw in node.keywords)
                self.dots.append((node.lineno, pref))


def _pallas_call_line(path: str, kernel_name: str) -> int:
    """Line of the ``pl.pallas_call`` site referencing ``kernel_name``
    in ``path`` (anchor for VMEM findings)."""
    try:
        tree = ast.parse(Path(path).read_text())
    except (OSError, SyntaxError):
        return 1
    fallback = 0
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            name = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else "")
            if name != "pallas_call":
                continue
            fallback = fallback or node.lineno
            if node.args and kernel_name in ast.unparse(node.args[0]):
                return node.lineno
    return fallback or 1


# ---------------------------------------------------------------------------
# the four contract checks over one capture
# ---------------------------------------------------------------------------

def _block_index(spec, gp) -> Tuple[int, ...]:
    return tuple(int(i) for i in spec.index_map(*gp))


def _spec_line(spec, default: int) -> int:
    # JAX wraps a BlockSpec's index map; the user's function (and its
    # source line) sits behind ``__wrapped__``
    code = getattr(inspect.unwrap(spec.index_map), "__code__", None)
    return code.co_firstlineno if code is not None else default


def _revisit_axes(spec, grid) -> List[int]:
    """Grid axes whose variation leaves the output block index fixed
    (probed at unit steps — all shipped index maps are affine)."""
    origin = _block_index(spec, (0,) * len(grid))
    out = []
    for a, n in enumerate(grid):
        if n < 2:
            continue
        probe = [0] * len(grid)
        probe[a] = 1
        if _block_index(spec, tuple(probe)) == origin:
            out.append(a)
    return out


def _check_race(cap: PallasCapture, ka: KernelAst) -> Iterable[Finding]:
    grid_pts = list(itertools.product(*(range(g) for g in cap.grid)))
    n_in, n_out = len(cap.in_specs), len(cap.out_shapes)

    def ref_name(pos: int) -> str:
        return ka.params[pos] if pos < len(ka.params) else f"<arg{pos}>"

    # (ref name, minor revisit axis, anchor line) needing init analysis
    revisited: List[Tuple[str, int, int]] = []
    for j, (spec, oshape) in enumerate(zip(cap.out_specs,
                                           cap.out_shapes)):
        line = _spec_line(spec, 1)
        seen: Dict[Tuple[int, ...], int] = {}
        for gp in grid_pts:
            idx = _block_index(spec, gp)
            seen[idx] = seen.get(idx, 0) + 1
        nblocks = tuple(
            max(1, -(-s // b))
            for s, b in zip(oshape.shape, spec.block_shape))
        uncovered = next(
            (blk for blk in itertools.product(
                *(range(n) for n in nblocks)) if blk not in seen),
            None)
        if uncovered is not None:
            yield Finding(
                cap.src_path, line, RACE,
                f"output {j} of {cap.kernel_name} "
                f"[{cap.probe_label}]: block {uncovered} is never "
                "written by any grid point",
                "make the output index map cover every block of the "
                "padded output, or shrink the out_shape")
        counts = set(seen.values())
        if len(counts) > 1:
            yield Finding(
                cap.src_path, line, RACE,
                f"output {j} of {cap.kernel_name} "
                f"[{cap.probe_label}]: irregular grid coverage "
                f"(visit counts {sorted(counts)})",
                "the output index map must visit every block the "
                "same number of times — revisit axes must be "
                "independent of the block index")
        if counts and max(counts) > 1:
            axes = _revisit_axes(spec, cap.grid)
            if axes:
                revisited.append((ref_name(n_in + j), max(axes), line))
    # scratch accumulators persist across the minor grid axis
    if cap.scratch and len(cap.grid) > 0:
        minor = len(cap.grid) - 1
        if cap.grid[minor] > 1:
            for s in range(len(cap.scratch)):
                revisited.append(
                    (ref_name(n_in + n_out + s), minor, 1))

    for name, t_axis, line in revisited:
        acc = ka.access.get(name, [])
        writes = [a for a in acc if a.kind in ("assign", "aug")]
        rmw = [a for a in acc if a.kind in ("read", "aug")]
        if not writes:
            continue
        var = next((v for v, ax in ka.pid_axes.items()
                    if ax == t_axis), None)
        inits = [a for a in writes
                 if a.kind == "assign" and a.guard == ("eq0", t_axis)]
        if rmw:
            if var is None or not inits:
                first = min(rmw, key=lambda a: a.line)
                yield Finding(
                    cap.src_path, first.line, RACE,
                    f"{name} in {cap.kernel_name} is revisited across "
                    f"grid axis {t_axis} and read/accumulated without "
                    "a first-visit init",
                    "initialize under @pl.when(pl.program_id("
                    f"{t_axis}) == 0) before any read-modify-write "
                    "(the kernels/gram.py revisiting pattern)")
                continue
            init_line = min(i.line for i in inits)
            # accesses that can run at the first visit must follow the
            # init textually (ne0/eq-guarded ones never see t == 0)
            unsafe = [a for a in rmw
                      if a.guard not in (("ne0", t_axis),
                                         ("eq", t_axis))
                      and a.line < init_line]
            if unsafe:
                first = min(unsafe, key=lambda a: a.line)
                yield Finding(
                    cap.src_path, first.line, RACE,
                    f"{name} in {cap.kernel_name} is read before its "
                    f"@pl.when == 0 init (line {init_line})",
                    "move the first-visit init above every "
                    "read-modify-write of the revisited ref")
        else:
            unguarded = [a for a in writes
                         if a.guard[1] != t_axis
                         or a.guard[0] in ("other", "none")]
            if unguarded:
                first = min(unguarded, key=lambda a: a.line)
                yield Finding(
                    cap.src_path, first.line, RACE,
                    f"{name} in {cap.kernel_name} is overwritten on "
                    f"every revisit of grid axis {t_axis} (no guard "
                    "on the revisit axis)",
                    "guard the write on the revisit axis (e.g. "
                    "@pl.when(t == n_blocks - 1) for a final-visit "
                    "write, as kernels/flash.py does) or accumulate "
                    "with a first-visit init")


def _check_bounds(cap: PallasCapture) -> Iterable[Finding]:
    grid_pts = list(itertools.product(*(range(g) for g in cap.grid)))
    shapes = [o.shape for o in cap.operands] + \
        [o.shape for o in cap.out_shapes]
    specs = list(cap.in_specs) + list(cap.out_specs)
    kinds = [f"input {i}" for i in range(len(cap.in_specs))] + \
        [f"output {i}" for i in range(len(cap.out_specs))]
    for spec, shape, kind in zip(specs, shapes, kinds):
        bshape = tuple(spec.block_shape)
        line = _spec_line(spec, 1)
        if len(bshape) != len(shape):
            yield Finding(
                cap.src_path, line, BOUNDS,
                f"{kind} of {cap.kernel_name} [{cap.probe_label}]: "
                f"block rank {len(bshape)} != operand rank "
                f"{len(shape)}",
                "block shape and operand must have the same rank")
            continue
        ragged = [d for d, (s, b) in enumerate(zip(shape, bshape))
                  if s % b]
        if ragged:
            yield Finding(
                cap.src_path, line, BOUNDS,
                f"{kind} of {cap.kernel_name} [{cap.probe_label}]: "
                f"operand shape {tuple(shape)} is not a multiple of "
                f"block {bshape} on axes {ragged}",
                "pad the operand through ops.pad_to_blocks before "
                "the pallas_call (padding must carry an exact no-op "
                "value, e.g. mask 0)")
            continue
        for gp in grid_pts:
            idx = _block_index(spec, gp)
            oob = [d for d, (i, b, s) in
                   enumerate(zip(idx, bshape, shape))
                   if i < 0 or (i + 1) * b > s]
            if oob:
                yield Finding(
                    cap.src_path, line, BOUNDS,
                    f"{kind} of {cap.kernel_name} "
                    f"[{cap.probe_label}]: grid point {gp} maps to "
                    f"block index {idx}, outside operand shape "
                    f"{tuple(shape)} on axes {oob}",
                    "fix the index map or the grid arithmetic — the "
                    "grid must be padded_shape // block, with the "
                    "padding done by ops.pad_to_blocks")
                break


def _check_dtype(cap: PallasCapture, ka: KernelAst,
                 seen_dots: set) -> Iterable[Finding]:
    import jax.numpy as jnp
    for line, pref in ka.dots:
        if (cap.src_path, line) in seen_dots:
            continue
        seen_dots.add((cap.src_path, line))
        if not pref:
            yield Finding(
                cap.src_path, line, DTYPE,
                f"contraction in {cap.kernel_name} without "
                "preferred_element_type=jnp.float32",
                "pass preferred_element_type=jnp.float32 so bf16/f16 "
                "operands accumulate in fp32 on the MXU")
    # across-grid accumulators (revisited outputs / scratch with
    # read-modify-write) must be fp32 when floating
    n_in, n_out = len(cap.in_specs), len(cap.out_shapes)
    refs: List[Tuple[int, Any, bool]] = []       # (pos, dtype, revisited)
    for j, (spec, oshape) in enumerate(zip(cap.out_specs,
                                           cap.out_shapes)):
        revis = bool(_revisit_axes(spec, cap.grid))
        refs.append((n_in + j, oshape.dtype, revis))
    minor_revis = len(cap.grid) > 0 and cap.grid[-1] > 1
    for s, (_, dt) in enumerate(cap.scratch):
        refs.append((n_in + n_out + s, dt, minor_revis))
    for pos, dt, revis in refs:
        if not revis:
            continue
        name = ka.params[pos] if pos < len(ka.params) else f"<arg{pos}>"
        acc = ka.access.get(name, [])
        if not any(a.kind in ("read", "aug") for a in acc):
            continue
        if jnp.issubdtype(dt, jnp.floating) and \
                jnp.dtype(dt) != jnp.dtype(jnp.float32):
            first = min((a for a in acc if a.kind in ("aug", "assign")),
                        key=lambda a: a.line, default=None)
            yield Finding(
                cap.src_path, first.line if first else 1, DTYPE,
                f"{name} in {cap.kernel_name} accumulates across the "
                f"grid in {jnp.dtype(dt).name}",
                "accumulate in a float32 ref (out_shape / scratch) "
                "and cast once at the final visit, as "
                "kernels/flash.py does for its bf16 output")


def _step_bytes(cap: PallasCapture) -> Dict[str, int]:
    """Per-grid-step resident VMEM estimate: Pallas double-buffers
    every in/out block (pipeline prefetch), scratch is single."""
    import jax.numpy as jnp

    def nbytes(shape, dtype):
        return math.prod(shape) * jnp.dtype(dtype).itemsize

    blocks = 0
    for spec, op in zip(cap.in_specs, cap.operands):
        blocks += nbytes(tuple(spec.block_shape), op.dtype)
    for spec, out in zip(cap.out_specs, cap.out_shapes):
        blocks += nbytes(tuple(spec.block_shape), out.dtype)
    scratch = sum(nbytes(s, d) for s, d in cap.scratch)
    return {"block_bytes": blocks, "scratch_bytes": scratch,
            "peak_bytes": 2 * blocks + scratch}


def _check_vmem(cap: PallasCapture, budget: int) -> Iterable[Finding]:
    est = _step_bytes(cap)
    if est["peak_bytes"] > budget:
        yield Finding(
            cap.src_path,
            _pallas_call_line(cap.src_path, cap.kernel_name), VMEM,
            f"{cap.kernel_name} [{cap.probe_label}]: estimated "
            f"{est['peak_bytes']} resident bytes per grid step "
            f"(2x{est['block_bytes']} double-buffered blocks + "
            f"{est['scratch_bytes']} scratch) exceeds the "
            f"{budget}-byte budget",
            "shrink the block sizes (the minor-axis tile is usually "
            "the lever) or raise the kernel's vmem_budget in the "
            "KERNELS registry with a measured justification")


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def _rule_ids(rules) -> set:
    if rules is None:
        return set(KERNEL_RULE_IDS)
    ids = {getattr(r, "id", r) for r in rules}
    return ids & set(KERNEL_RULE_IDS)


def check_spec(spec, rules=None) -> List[Finding]:
    """Run the four contract checks over one registry entry."""
    want = _rule_ids(rules)
    if not want:
        return []
    findings: List[Finding] = []
    seen_dots: set = set()
    asts: Dict[Tuple[str, str], KernelAst] = {}
    for cap in capture_spec(spec):
        key = (cap.src_path, cap.kernel_name)
        if key not in asts:
            asts[key] = KernelAst(*key)
        ka = asts[key]
        if ka.fn is None:
            findings.append(Finding(
                cap.src_path, 1, RACE,
                f"kernel function {cap.kernel_name} not found in "
                "source — guard analysis impossible",
                "define the kernel as a module-level def in the file "
                "that issues its pallas_call"))
            continue
        if RACE in want:
            findings.extend(_check_race(cap, ka))
        if BOUNDS in want:
            findings.extend(_check_bounds(cap))
        if DTYPE in want:
            findings.extend(_check_dtype(cap, ka, seen_dots))
        if VMEM in want:
            findings.extend(_check_vmem(cap, spec.vmem_budget))
    return _dedupe_suppress(findings)


def _dedupe_suppress(findings: Sequence[Finding]) -> List[Finding]:
    """Drop duplicate (path, line, rule) findings across probes and
    honor ``# repro-lint: disable=`` comments in the kernel source."""
    lines_cache: Dict[str, List[str]] = {}
    supp_cache: Dict[str, Dict[int, set]] = {}
    out: List[Finding] = []
    seen = set()
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule)):
        key = (f.path, f.line, f.rule)
        if key in seen:
            continue
        seen.add(key)
        if f.path not in lines_cache:
            try:
                lines_cache[f.path] = \
                    Path(f.path).read_text().splitlines()
            except OSError:
                lines_cache[f.path] = []
            supp_cache[f.path] = invariants._suppressions(
                lines_cache[f.path])
        if invariants._suppressed(f, lines_cache[f.path],
                                  supp_cache[f.path]):
            continue
        out.append(f)
    return out


def check_kernels(registry=None, rules=None) -> List[Finding]:
    """Verify every registered kernel (default: the shipped
    ``repro.kernels.ops.KERNELS`` registry)."""
    if registry is None:
        from ..kernels.ops import KERNELS as registry
    findings: List[Finding] = []
    for spec in registry.values():
        findings.extend(check_spec(spec, rules))
    return findings


def _load_registry(path: Path):
    """Import a standalone kernel file (fixtures) and return its
    ``KERNELS`` registry."""
    name = f"_repro_kernel_fixture_{path.stem}"
    modspec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    registry = getattr(mod, "KERNELS", None)
    if not isinstance(registry, dict) or not registry:
        raise ValueError(
            f"{path}: kernel files must define a KERNELS registry "
            "(dict of repro.kernels.ops.KernelSpec); see "
            "tests/fixtures/analysis/kernel_bad_*.py")
    return registry


def check_kernel_paths(paths: Sequence[Path],
                       rules=None) -> List[Finding]:
    """Verify standalone kernel files carrying their own ``KERNELS``
    registry (how the seeded-violation fixtures are checked)."""
    findings: List[Finding] = []
    for p in paths:
        findings.extend(check_kernels(_load_registry(Path(p)), rules))
    return findings


# ---------------------------------------------------------------------------
# VMEM report for the dry-run records
# ---------------------------------------------------------------------------

_VMEM_MEMO: Dict[int, Dict[str, Dict[str, object]]] = {}


def vmem_report(registry=None) -> Dict[str, Dict[str, object]]:
    """Per-kernel worst-case VMEM estimate over the registry probes —
    the ``kernel_vmem`` column of every ``results/dryrun/*.json``
    (``contract.dryrun_contract_findings`` re-derives and audits it).
    """
    if registry is None:
        from ..kernels.ops import KERNELS as registry
    memo_key = id(registry)
    if memo_key in _VMEM_MEMO:
        return _VMEM_MEMO[memo_key]
    report: Dict[str, Dict[str, object]] = {}
    for name, spec in registry.items():
        peak = {"peak_bytes": 0, "block_bytes": 0, "scratch_bytes": 0}
        config = ""
        for cap in capture_spec(spec):
            est = _step_bytes(cap)
            if est["peak_bytes"] > peak["peak_bytes"]:
                peak, config = est, cap.probe_label
        report[name] = {
            **peak, "config": config,
            "budget_bytes": spec.vmem_budget,
            "ok": peak["peak_bytes"] <= spec.vmem_budget}
    _VMEM_MEMO[memo_key] = report
    return report
