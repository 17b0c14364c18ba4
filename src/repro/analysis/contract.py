"""Declarative communication contracts for the sharded Gibbs sweep.

A :class:`CommContract` states, per compiled sweep step, exactly what
may cross the interconnect — the limited-communication guarantee of
the subset-parallel MCMC literature (arXiv:2004.02561) that PR 4's
ring pipeline made exact:

* ``all_gathers``       — full-factor gathers per sweep: one per
                          entity in eager mode, **zero** in ring mode;
* ``collective_permutes`` — ring hops: ``E * (S - 1)`` in ring mode;
* ``all_reduces``       — hyper-moment + metric psums: per entity
                          2 (Normal), 3 (Macau), 2 (SpikeAndSlab),
                          0 (FixedNormal), plus 2 scalar psums per
                          block (SSE, nnz);
* ``max_reduce_elems``  — largest all-reduce payload in elements
                          (K² Normal/Wishart moments, max(K², D·K)
                          Macau, K SpikeAndSlab) — this is the pin
                          that keeps e.g. the Macau FtF (D×D) product
                          hoisted out of the psum;
* ``wire_dtype``        — exchange dtype on gather/permute wires
                          (``bf16`` when ``ModelDef.bf16_gather``);
* ``chains``            — chains swept PER SHARD GROUP per step call
                          (``distributed.make_multi_chain_step`` maps
                          the per-chain sweep with ``lax.map``): every
                          count above is the total across those local
                          chains, while per-op payloads are unchanged
                          (each chain runs its own psums).  With a
                          chain mesh axis the chains spread over it,
                          so the local multiplier drops to
                          ``C / axis_size`` and the row-shard count
                          ``S`` shrinks to the per-chain shard group.

:func:`contract_for` *derives* the contract from any ``ModelDef`` —
no per-model pins — and the two checkers verify it against StableHLO
(exact op counts before backend scheduling) and compiled HLO (via
:func:`repro.launch.hlo_cost.parse_module`, trip-count-aware so
scan-rolled rings at 256 shards count correctly; all-reduce *counts*
are not checked on compiled HLO because backends may legally combine
payloads, but payload *sizes* are).
"""
from __future__ import annotations

import dataclasses
import json
import math
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from ..core.distributed import RING_UNROLL_MAX, resolve_pipeline
from ..core.priors import (FixedNormalPrior, MacauPrior, NormalPrior,
                           SpikeAndSlabPrior)
from ..launch.hlo_cost import COLLECTIVES, _called, _trip_count, \
    op_kind, parse_module


class ContractViolation(AssertionError):
    """Raised by :func:`assert_contract` with one line per violation."""


@dataclasses.dataclass(frozen=True)
class CommContract:
    pipeline: str
    n_shards: int
    all_gathers: int            # full-factor gathers per sweep
    collective_permutes: int    # ring hops per sweep
    all_reduces: int            # hyper-moment + metric psums
    max_reduce_elems: int       # largest all-reduce payload (elems)
    wire_dtype: str             # "f32" | "bf16" on gather/permute
    chains: int = 1             # local chains per shard group; the
    #                             counts above are totals across them

    def asdict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


def _prior_reduce_profile(prior) -> Tuple[int, int]:
    """(all-reduce count, max payload elems) for one entity's hyper
    moments, as emitted by ``distributed._psum_hyper``."""
    K = getattr(prior, "num_latent", 0)
    if isinstance(prior, MacauPrior):
        D = prior.num_features
        # sum_U (K), moment (K,K), side moment (D,K); the side's Gram
        # and column sums are static data (gibbs.side_statistics)
        return 3, max(K * K, D * K, K)
    if isinstance(prior, SpikeAndSlabPrior):
        return 2, K                    # slab mass (K) + counts (K)
    if isinstance(prior, FixedNormalPrior):
        return 0, 0                    # no hypers to resample
    if isinstance(prior, NormalPrior):
        return 2, K * K                # sum_U (K) + moment (K,K)
    raise ValueError(
        f"no communication profile for prior {type(prior).__name__}; "
        "supported priors: "
        + ", ".join(sorted(c.__name__ for c in (
            NormalPrior, MacauPrior, SpikeAndSlabPrior,
            FixedNormalPrior))))


def contract_for(model, mesh_shape: Sequence[int],
                 pipeline: Optional[str] = "eager",
                 chains: int = 1,
                 chain_axis_size: Optional[int] = None) -> CommContract:
    """Derive the expected communication contract for one sweep of
    ``model`` sharded over ``mesh_shape`` under ``pipeline``.

    Pure arithmetic over the ModelDef — E entities, M blocks,
    S = prod(mesh_shape) shards — so it needs no devices and works
    for any model the builder can express.

    ``chains=C`` (``make_multi_chain_step``): every shard sweeps its
    local chains serially (``lax.map``), so collective COUNTS scale by
    the local chain multiplier while per-op payloads stay fixed.
    ``chain_axis_size`` declares that ``mesh_shape`` includes a chain
    mesh axis of that size: rows then shard over only the remaining
    ``S = prod(mesh_shape) / chain_axis_size`` devices and each shard
    group sweeps ``C / chain_axis_size`` chains — how chains x shards
    fills a pod without inflating the per-group census.
    """
    pipeline = resolve_pipeline(pipeline)
    n_shards = math.prod(mesh_shape)
    chains = int(chains)
    if chains < 1:
        raise ValueError(f"chains must be >= 1, got {chains}")
    if chain_axis_size is not None:
        if n_shards % chain_axis_size:
            raise ValueError(
                f"chain_axis_size={chain_axis_size} does not divide "
                f"the {n_shards}-device mesh {tuple(mesh_shape)}")
        if chains % chain_axis_size:
            raise ValueError(
                f"chains={chains} does not divide over a chain axis "
                f"of size {chain_axis_size}")
        n_shards //= chain_axis_size
        local = chains // chain_axis_size
    else:
        local = chains
    E, M = len(model.entities), len(model.blocks)
    ar, elems = 0, 0
    for ent in model.entities:
        n, e = _prior_reduce_profile(ent.prior)
        ar += n
        elems = max(elems, e)
    ar += 2 * M                        # SSE + nnz scalars per block
    elems = max(elems, 1) if ar else elems
    if pipeline == "ring":
        ag, cp = 0, E * (n_shards - 1)
    else:
        ag, cp = E, 0
    return CommContract(
        pipeline=pipeline, n_shards=n_shards, all_gathers=ag * local,
        collective_permutes=cp * local, all_reduces=ar * local,
        max_reduce_elems=elems,
        wire_dtype="bf16" if model.bf16_gather else "f32",
        chains=local)


def contract_wire_bytes(model, contract: CommContract) -> int:
    """Estimated bytes RECEIVED per device per sweep under ``contract``.

    The number the obs subsystem stamps into every sweep span
    (``args.bytes_on_wire``), so traces carry the expected collective
    volume next to the measured wall time.  Derivation, per shard:

    * fixed-factor exchange — eager all-gather and ring ppermute move
      the same total: each entity's full factor minus the shard's own
      rows, ``n_rows * K * itemsize * (S-1)/S``, once per local chain;
    * all-reduces — ``all_reduces`` ops (already scaled by local
      chains) of at most ``max_reduce_elems`` f32 elements each, ring
      cost ``(S-1)/S`` per pass (one-pass estimate: an upper bound on
      payload, a lower bound on passes — collectives on real fabrics
      are within a small factor either way).

    ``S == 1`` (or no mesh) → 0: nothing crosses a wire.
    """
    S = contract.n_shards
    if S <= 1:
        return 0
    frac = (S - 1) / S
    item = 2 if contract.wire_dtype == "bf16" else 4
    fixed_elems = sum(e.n_rows * model.num_latent
                      for e in model.entities)
    exchange = fixed_elems * item * frac * contract.chains
    reduces = contract.all_reduces * contract.max_reduce_elems * 4 * frac
    return int(exchange + reduces)


# ---------------------------------------------------------------------------
# StableHLO check (pre-backend: exact op counts)
# ---------------------------------------------------------------------------

def check_lowered(contract: CommContract, text: str) -> List[str]:
    """Verify a StableHLO module (``lowered.as_text()``) against the
    contract.  Counts are exact here — nothing has been combined or
    split yet.  Note: ring pipelines above ``RING_UNROLL_MAX`` shards
    lower to ``stablehlo.while`` loops; use :func:`check_compiled`
    (trip-count-aware) for those.

    A multi-chain sweep (``contract.chains > 1``) ``lax.map``-rolls
    the per-chain body into ONE ``stablehlo.while``, so the text holds
    per-iteration counts — the contract's totals divided by the local
    chain count (compiled HLO recovers the trip count and checks the
    totals directly).
    """
    lines = text.splitlines()
    ag = [ln for ln in lines if "stablehlo.all_gather" in ln]
    cp = [ln for ln in lines if "stablehlo.collective_permute" in ln]
    ar = sum(ln.count("stablehlo.all_reduce") for ln in lines)
    rolled_ring = (contract.pipeline == "ring"
                   and contract.n_shards > RING_UNROLL_MAX)
    local = max(1, contract.chains)
    out: List[str] = []
    if len(ag) * local != contract.all_gathers:
        out.append(f"stablehlo: {len(ag)} all-gathers per chain "
                   f"iteration, contract says "
                   f"{contract.all_gathers} across {local} chain(s)")
    if not rolled_ring and len(cp) * local \
            != contract.collective_permutes:
        out.append(f"stablehlo: {len(cp)} collective-permutes per "
                   f"chain iteration, contract says "
                   f"{contract.collective_permutes} across {local} "
                   "chain(s)")
    if ar * local != contract.all_reduces:
        out.append(f"stablehlo: {ar} all-reduces per chain iteration, "
                   f"contract says {contract.all_reduces} across "
                   f"{local} chain(s)")
    want_bf16 = contract.wire_dtype == "bf16"
    for ln in ag + cp:
        if ("bf16" in ln) != want_bf16:
            out.append("stablehlo: exchange wire is not "
                       f"{contract.wire_dtype}: {ln.strip()[:100]}")
    return out


# ---------------------------------------------------------------------------
# compiled-HLO check (post-SPMD: trip-count-aware, parse_module-based)
# ---------------------------------------------------------------------------

def _collect_collectives(text: str):
    """Trip-count-aware collective census over compiled HLO text:
    ``({kind: count}, {kind: max payload elems})``.  Built on
    ``hlo_cost.parse_module`` — async ``-start``/``-done`` pairs are
    counted once, ``while`` bodies multiply by the recovered trip
    count (how a scan-rolled ring at S=256 still counts E*(S-1))."""
    comps = parse_module(text)
    cache: Dict[str, Tuple[Dict[str, float], Dict[str, int]]] = {}

    def merge(counts, elems, sub, mult=1):
        sc, se = sub
        for k, v in sc.items():
            counts[k] = counts.get(k, 0) + mult * v
        for k, v in se.items():
            elems[k] = max(elems.get(k, 0), v)

    def visit(name: str):
        if name in cache:
            return cache[name]
        counts: Dict[str, float] = {}
        elems: Dict[str, int] = {}
        cache[name] = (counts, elems)   # guards (impossible) cycles
        for ins in comps.get(name, []):
            kind = op_kind(ins.op)
            if kind in COLLECTIVES and not ins.op.endswith("-done"):
                counts[kind] = counts.get(kind, 0) + 1
                m = max((s.elems for s in ins.shapes), default=0)
                elems[kind] = max(elems.get(kind, 0), m)
            if ins.op == "while":
                mt = re.search(
                    r'known_trip_count[^}]*?"n"\s*:\s*"(\d+)"',
                    ins.attrs)
                trip = int(mt.group(1)) if mt else None
                cond = _called(ins.attrs, "condition")
                if trip is None and cond and cond in comps:
                    trip = _trip_count(comps[cond])
                trip = trip if trip else 1
                for key in ("body", "condition"):
                    callee = _called(ins.attrs, key)
                    if callee:
                        merge(counts, elems, visit(callee), trip)
            elif ins.op == "fusion":
                callee = _called(ins.attrs, "calls")
                if callee:
                    merge(counts, elems, visit(callee))
            elif ins.op in ("call", "async-start"):
                callee = _called(ins.attrs, "calls") or \
                    _called(ins.attrs, "to_apply")
                if callee:
                    merge(counts, elems, visit(callee))
            elif ins.op == "conditional":
                for key in ("true_computation", "false_computation"):
                    callee = _called(ins.attrs, key)
                    if callee:
                        merge(counts, elems, visit(callee))
        return counts, elems

    entry = "ENTRY" if "ENTRY" in comps else next(iter(comps), None)
    if entry is None:
        return {}, {}
    return visit(entry)


def check_compiled(contract: CommContract, text: str) -> List[str]:
    """Verify compiled HLO (``compiled.as_text()``) against the
    contract.  all-gather / collective-permute counts are exact (trip
    multiplied); all-reduce payload sizes are bounded by
    ``max_reduce_elems`` (counts may legally differ — backends
    combine psums)."""
    counts, elems = _collect_collectives(text)
    out: List[str] = []
    n_ag = int(counts.get("all-gather", 0))
    n_cp = int(counts.get("collective-permute", 0))
    if n_ag != contract.all_gathers:
        out.append(f"compiled: {n_ag} all-gathers, contract says "
                   f"{contract.all_gathers}")
    if n_cp != contract.collective_permutes:
        out.append(f"compiled: {n_cp} collective-permutes, contract "
                   f"says {contract.collective_permutes}")
    got = elems.get("all-reduce", 0)
    if got > contract.max_reduce_elems:
        out.append(f"compiled: all-reduce payload of {got} elems "
                   f"exceeds contract max {contract.max_reduce_elems}"
                   " (a full-matrix product leaked into a psum?)")
    return out


def assert_contract(contract: CommContract,
                    lowered_text: Optional[str] = None,
                    compiled_text: Optional[str] = None,
                    where: str = "") -> None:
    """Raise :class:`ContractViolation` listing every violation of
    ``contract`` in the given StableHLO and/or compiled HLO text."""
    out: List[str] = []
    if lowered_text is not None:
        out.extend(check_lowered(contract, lowered_text))
    if compiled_text is not None:
        out.extend(check_compiled(contract, compiled_text))
    if out:
        head = f"{where}: " if where else ""
        raise ContractViolation(
            head + f"{len(out)} contract violation(s) against "
            f"{contract}\n  " + "\n  ".join(out))


# ---------------------------------------------------------------------------
# dry-run JSON audit (CI: results/dryrun/*.json carry their contract)
# ---------------------------------------------------------------------------

def dryrun_contract_findings(json_path) -> List[str]:
    """Audit one dry-run record: its stored ``contract`` column must
    match a freshly derived ``contract_for`` and its generation-time
    HLO check must have passed.  Imports ``mf_dryrun`` lazily (the
    module pins a 512-device host platform via XLA_FLAGS at import —
    harmless here, no devices are materialized)."""
    p = Path(json_path)
    rec = json.loads(p.read_text())
    if "error" in rec:
        return [f"{p}: dry-run record is an error record"]
    out: List[str] = []
    if "contract" not in rec:
        return [f"{p}: missing contract column — regenerate with "
                "`python -m repro.launch.mf_dryrun`"]
    from ..launch.mf_dryrun import CELLS, build_model
    arch = rec.get("arch", "")
    name = arch[3:] if arch.startswith("mf_") else arch
    if name not in CELLS:
        return [f"{p}: unknown cell {name!r}; valid cells: "
                f"{', '.join(sorted(CELLS))}"]
    model = build_model(CELLS[name], rec.get("variant", "baseline"))
    mesh_shape = tuple(int(x) for x in rec["mesh"].split("x"))
    derived = contract_for(
        model, mesh_shape, rec.get("pipeline", "eager"),
        chains=rec.get("chains", 1),
        chain_axis_size=rec.get("chain_axis_size")).asdict()
    # records written before the multi-chain column default to one
    # chain per shard group
    stored = {"chains": 1, **rec["contract"]}
    for k, v in derived.items():
        if stored.get(k) != v:
            out.append(f"{p}: contract[{k!r}] = {stored.get(k)!r} "
                       f"but derivation says {v!r}")
    if not rec.get("contract_ok", False):
        out.append(f"{p}: contract_ok is not true — the compiled "
                   "HLO violated its contract at generation time: "
                   f"{rec.get('contract_violations')}")
    out.extend(_kernel_vmem_findings(p, rec))
    return out


def _kernel_vmem_findings(p: Path, rec: dict) -> List[str]:
    """Audit the ``kernel_vmem`` column (PR 8): the per-kernel VMEM
    estimates baked into the record must match a fresh
    ``kernelcheck.vmem_report`` over the shipped registry (memoized —
    one capture pass covers all audited JSONs) and every kernel must
    be inside its budget."""
    out: List[str] = []
    if "kernel_vmem" not in rec:
        return [f"{p}: missing kernel_vmem column — regenerate with "
                "`python -m repro.launch.mf_dryrun`"]
    from .kernelcheck import vmem_report
    fresh = vmem_report()
    stored = rec["kernel_vmem"]
    for name, want in fresh.items():
        got = stored.get(name)
        if got is None:
            out.append(f"{p}: kernel_vmem missing kernel {name!r}")
            continue
        for k in ("peak_bytes", "budget_bytes", "ok"):
            if got.get(k) != want[k]:
                out.append(
                    f"{p}: kernel_vmem[{name!r}][{k!r}] = "
                    f"{got.get(k)!r} but a fresh estimate says "
                    f"{want[k]!r}")
    if not rec.get("kernel_vmem_ok", False):
        out.append(f"{p}: kernel_vmem_ok is not true — a kernel "
                   "blew its VMEM budget at generation time")
    return out
