import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_BASE_XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# placeholder devices on the host CPU, never an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST run before any jax import (jax locks device count on first init).

"""Multi-pod dry-run of the PAPER'S OWN WORKLOAD: the distributed
Bayesian-MF Gibbs sweep at compound-activity production scale.

The LM-architecture dry-run (dryrun.py) covers the assigned pool; this
module proves the SMURFF core itself distributes: ChEMBL-scale cells
(paper §4 Macau: >1M compounds x thousands of proteins, ECFP side
info) lowered + compiled on the 16x16 single-pod and 2x16x16 multi-pod
meshes, with the same roofline extraction.

Cells:
    bmf_chembl     1,048,576 x 8,192, K=128, ~67M observed entries
    macau_chembl   + 2048-bit ECFP side info on the compound axis
                   (the side-Gramian FtF and column sums are built
                   with the data — no per-sweep (D, D) or D psum)
    probit_chembl  binary activity classification (paper §4): same
                   shape, ProbitNoise with counter-based truncated-
                   normal augmentation — runs the explicit sharded
                   sweep, not the pjit fallback
    dense_views    131,072 x 4,096 fully-observed dense block
                   ("dense-dense" row of Table 1) through the sharded
                   dense path (row-sharded orientations, one shared
                   (K, K) Gram per half-sweep)
    gfa_views      GFA multi-view workload (Table 1 "Normal + SnS"):
                   131,072 samples x 3 views (8192/4096/2048 features),
                   FixedNormal on the shared Z, spike-and-slab on every
                   loading matrix — the counter-based coordinate update
                   runs the explicit sharded sweep (one all-gather per
                   half-sweep, two K-sized hyper psums per view, zero
                   per-component collectives), not a pjit fallback

Variants:
    baseline      row-sharded factors, f32 fixed-factor all-gather
                  (the GASPI communication pattern, Vander Aa 2017)
    bf16gather    fixed factor cast to bf16 *before* the all-gather
                  (halves the dominant collective payload on targets
                  with native bf16 collectives, i.e. TPU; XLA:CPU —
                  this container — normalizes the collective back to
                  convert-gather-convert, so the recorded
                  collective_bytes do NOT drop here.  The bf16
                  exchange is pinned on the lowered StableHLO in
                  tests/test_distributed.py instead.)
    ring          the fixed-factor exchange travels as n_shards - 1
                  double-buffered ``ppermute`` hops instead of one
                  blocking all-gather (``pipeline="ring"`` on
                  ``make_distributed_step``) — same wire bytes, zero
                  all-gathers, and each hop is issued before the
                  previous chunk is consumed so the exchange hides
                  behind local work.
    chains4       four independent Gibbs chains through
                  ``make_multi_chain_step`` on a ("chain", 4) x
                  ("data", S/4) mesh — chains x shards fills the pod,
                  each 64-shard group sweeps ONE local chain, so the
                  per-group collective census equals the single-chain
                  census at 64 shards (``contract_for(..., chains=4,
                  chain_axis_size=4)``) while useful FLOPs scale by 4.
                  This is the convergence-diagnostics posture: R-hat /
                  ESS need >= 2 chains (``core.diagnostics``).

Exchange model (per-sweep per-device seconds, in every record):
    exchange_s_serial   collective_bytes / ICI bandwidth — the wire time,
                        which the eager pipeline fully EXPOSES (the
                        blocking all-gather precedes every row solve
                        of its half-sweep)
    exchange_s_modeled  the exposed exchange time after overlap:
                        equal to exchange_s_serial for eager;
                        max(serial - max(compute_s, memory_s), 0) for
                        ring, whose hops overlap the chunk-accumulated
                        Gram/RHS math and local solves.  Eager stays
                        the session default until this term wins on
                        the deploy target.

Usage:
    PYTHONPATH=src python -m repro.launch.mf_dryrun [--cell bmf_chembl]
        [--mesh single|multi|both]
        [--variant baseline|bf16gather|ring]
"""
import argparse
import dataclasses
import json
from ..obs import clock
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# the device kind whose published peaks turn counts into modeled seconds
DEVICE_KIND = "TPU v5 lite"

F32 = jnp.float32
I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class MFCell:
    name: str
    n_rows: int
    n_cols: int
    K: int
    row_nnz: int          # padded nonzeros per row
    col_nnz: int          # padded nonzeros per column
    nnz_pad: int          # flat COO padding
    side_feats: int = 0   # Macau fingerprints on the row axis
    probit: bool = False  # binary data, ProbitNoise augmentation
    dense: bool = False   # fully-observed DenseBlock payload
    gfa_dims: tuple = ()  # GFA view widths (SnS loadings per view)


CELLS = {
    "bmf_chembl": MFCell("bmf_chembl", 1 << 20, 8192, 128, 64, 8192,
                         1 << 26),
    "macau_chembl": MFCell("macau_chembl", 1 << 20, 8192, 128, 64, 8192,
                           1 << 26, side_feats=2048),
    "probit_chembl": MFCell("probit_chembl", 1 << 20, 8192, 128, 64,
                            8192, 1 << 26, probit=True),
    "dense_views": MFCell("dense_views", 1 << 17, 4096, 128, 0, 0, 0,
                          dense=True),
    # GFA latent dim is small in practice (Table 1 runs K ~ 10-30);
    # K=32 also bounds the unrolled per-component coordinate loop
    "gfa_views": MFCell("gfa_views", 1 << 17, 8192, 32, 0, 0, 0,
                        dense=True, gfa_dims=(8192, 4096, 2048)),
}


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_data(cell: MFCell):
    """MFData of ShapeDtypeStructs at full production size."""
    from ..core.blocks import DenseBlock
    from ..core.sparse import PaddedRows, SparseMatrix
    from ..core.gibbs import make_data

    if cell.gfa_dims:
        N = cell.n_rows
        blks = tuple(
            DenseBlock(_sds((N, D), F32), _sds((N, D), F32),
                       _sds((D, N), F32), _sds((D, N), F32), fully=True)
            for D in cell.gfa_dims)
        return make_data(blks, (None,) * (1 + len(cell.gfa_dims)))

    if cell.dense:
        R, C = cell.n_rows, cell.n_cols
        blk = DenseBlock(_sds((R, C), F32), _sds((R, C), F32),
                         _sds((C, R), F32), _sds((C, R), F32),
                         fully=True)
        return make_data((blk,), (None, None))

    rows = PaddedRows(_sds((cell.n_rows, cell.row_nnz), I32),
                      _sds((cell.n_rows, cell.row_nnz), F32),
                      _sds((cell.n_rows, cell.row_nnz), F32),
                      n_other=cell.n_cols)
    cols = PaddedRows(_sds((cell.n_cols, cell.col_nnz), I32),
                      _sds((cell.n_cols, cell.col_nnz), F32),
                      _sds((cell.n_cols, cell.col_nnz), F32),
                      n_other=cell.n_rows)
    E = cell.nnz_pad
    mat = SparseMatrix(rows, cols, _sds((E,), I32), _sds((E,), I32),
                       _sds((E,), F32), _sds((E,), F32),
                       _sds((E,), I32), _sds((E,), I32),
                       shape=(cell.n_rows, cell.n_cols))
    side = _sds((cell.n_rows, cell.side_feats), F32) \
        if cell.side_feats else None
    return make_data((mat,), (side, None))


def build_model(cell: MFCell, variant: str):
    from ..core.blocks import BlockDef, EntityDef, ModelDef
    from ..core.noise import AdaptiveGaussian, ProbitNoise
    from ..core.priors import (FixedNormalPrior, MacauPrior, NormalPrior,
                               SpikeAndSlabPrior)
    if cell.gfa_dims:
        ents = [EntityDef("samples", cell.n_rows,
                          FixedNormalPrior(cell.K))]
        blocks = []
        for m, D in enumerate(cell.gfa_dims):
            ents.append(EntityDef(f"view{m}", D,
                                  SpikeAndSlabPrior(cell.K)))
            blocks.append(BlockDef(0, m + 1, AdaptiveGaussian(),
                                   sparse=False))
        return ModelDef(tuple(ents), tuple(blocks), cell.K,
                        use_pallas=False,
                        bf16_gather=("bf16gather" in variant))
    rp = MacauPrior(cell.K, cell.side_feats) if cell.side_feats \
        else NormalPrior(cell.K)
    noise = ProbitNoise() if cell.probit else AdaptiveGaussian()
    return ModelDef(
        (EntityDef("compounds", cell.n_rows, rp),
         EntityDef("proteins", cell.n_cols, NormalPrior(cell.K))),
        (BlockDef(0, 1, noise, sparse=not cell.dense),),
        cell.K, use_pallas=False,
        bf16_gather=("bf16gather" in variant))


def mf_model_flops(cell: MFCell, n_chips: int) -> float:
    """Useful FLOPs per device per sweep (both half-sweeps).

    Gram 2*K^2 + rhs 2*K per nonzero per orientation, Cholesky K^3/3
    + two triangular solves 2*K^2 per row, one SDDMM 2*K per entry.
    Fully-observed dense blocks instead share one (K, K) Gram per
    half-sweep and regress every cell: rhs 2*K per cell per
    orientation + residual 2*K per cell.
    """
    K = cell.K
    if cell.gfa_dims:
        # Z update: per-view shared Gram + RHS, one Cholesky per row;
        # SnS loadings: the coordinate loop touches every cell ~8x
        # per component (pred downdate, l, pred restore; the q term is
        # one shared scalar on fully-observed views), all row-local;
        # metrics one residual pass
        N = cell.n_rows
        tot = N * (K ** 3 / 3 + 2 * K * K)
        for D in cell.gfa_dims:
            cells_ = N * D
            tot += 2 * D * K * K + 2 * cells_ * K
            tot += 8 * K * cells_
            tot += 2 * cells_ * K
        return tot / n_chips
    if cell.dense:
        cells_ = cell.n_rows * cell.n_cols
        gram = (2 * (cell.n_rows + cell.n_cols) * K * K
                + 4 * cells_ * K)
        chol = (cell.n_rows + cell.n_cols) * (K ** 3 / 3 + 2 * K * K)
        return (gram + 2 * cells_ * K + chol) / n_chips
    nnz = cell.nnz_pad                      # padded upper bound
    gram = 2 * nnz * (2 * K * K + 2 * K)
    chol = (cell.n_rows + cell.n_cols) * (K ** 3 / 3 + 2 * K * K)
    sddmm = 2 * nnz * K
    beta = 0.0
    if cell.side_feats:
        D = cell.side_feats
        beta = 2 * cell.n_rows * D * K + D ** 3 / 3
    return (gram + chol + sddmm + beta) / n_chips


def lower_cell(cell: MFCell, mesh, variant: str):
    from ..analysis.contract import check_compiled, contract_for
    from ..core.distributed import (distributed_supported,
                                    make_distributed_step,
                                    make_multi_chain_step)
    from ..core.gibbs import init_chain_states, init_state, stack_states
    from .hlo_cost import analyze as hlo_analyze
    from .roofline import peaks_for

    model = build_model(cell, variant)
    data = abstract_data(cell)
    pipeline = "ring" if "ring" in variant else "eager"
    chains = 4 if "chains4" in variant else 1
    chain_axis = "chain" if chains > 1 else None

    peaks = peaks_for(DEVICE_KIND)
    t0 = clock.perf_counter()
    # explicit shard_map sweep (one fixed-factor exchange per
    # half-sweep + K/K^2 moment psums); production cells are always in
    # the sharded subset — assert rather than silently fall back to the
    # auto-partitioned path whose collectives we are here to measure.
    assert distributed_supported(model, mesh, data), cell.name
    if chains > 1:
        state = jax.eval_shape(lambda: stack_states(
            init_chain_states(model, data, 0, chains)))
        step, ds, ss = make_multi_chain_step(
            model, mesh, data, state, pipeline=pipeline,
            chains=chains, chain_axis=chain_axis)
    else:
        state = jax.eval_shape(lambda: init_state(model, data, 0))
        step, ds, ss = make_distributed_step(model, mesh, data, state,
                                             pipeline=pipeline)
    lowered = step.lower(data, state)
    t_lower = clock.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = clock.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    ctxt = compiled.as_text()
    hc = hlo_analyze(ctxt)
    # the derived communication contract, verified against the very
    # HLO whose roofline we are recording (trip-count-aware, so the
    # scan-rolled ring at 256 shards counts its E*(S-1) hops)
    cas = int(mesh.shape[chain_axis]) if chain_axis else None
    contract = contract_for(model, tuple(mesh.devices.shape), pipeline,
                            chains=chains, chain_axis_size=cas)
    violations = check_compiled(contract, ctxt)
    n_chips = mesh.devices.size
    bytes_hbm = (hc["bytes_materialized"]
                 + int(mem.argument_size_in_bytes)
                 + int(mem.output_size_in_bytes))
    comp = hc["flops"] / peaks.flops
    memt = bytes_hbm / peaks.hbm_bw
    coll = hc["collective_bytes"]["total"] / peaks.ici_bw
    # overlap-aware exchange term: the eager all-gather blocks the
    # half-sweep it feeds (fully exposed wire time); the ring's
    # ppermute hops are double-buffered against the chunk-accumulated
    # moment math and local solves, exposing only what the local work
    # cannot cover (see module docstring)
    exchange = coll if pipeline == "eager" \
        else max(coll - max(comp, memt), 0.0)
    # C chains sweep C posteriors — per-device useful FLOPs scale by C
    # (each of the S/axis_size-shard groups sweeps its local chains)
    mf = mf_model_flops(cell, n_chips) * chains
    bound = max(comp, memt, coll)
    rec = {
        "arch": f"mf_{cell.name}", "shape": "gibbs_sweep",
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "kind": "mf", "variant": variant, "pipeline": pipeline,
        "n_chips": int(n_chips),
        "flops": hc["flops"],
        "bytes_accessed": hc["bytes_accessed"],
        "bytes_hbm": bytes_hbm,
        "collective_bytes": hc["collective_bytes"],
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                     + mem.temp_size_in_bytes
                                     + mem.output_size_in_bytes),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "compute_s": comp, "memory_s": memt, "collective_s": coll,
        "exchange_s_serial": coll, "exchange_s_modeled": exchange,
        "dominant": max(("compute", comp), ("memory", memt),
                        ("collective", coll), key=lambda kv: kv[1])[0],
        "model_flops": mf,
        "useful_flop_ratio": mf / hc["flops"] if hc["flops"] else 0.0,
        "roofline_fraction": (mf / peaks.flops) / bound if bound else 0.0,
        "contract": contract.asdict(),
        "contract_ok": not violations,
    }
    if chains > 1:
        rec["chains"] = chains
        rec["chain_axis_size"] = cas
    if violations:
        rec["contract_violations"] = violations
    # audited per-kernel VMEM estimates (PR 8): the same report the
    # `python -m repro.analysis` dry-run audit re-derives and compares
    from ..analysis.kernelcheck import vmem_report
    kv = vmem_report()
    rec["kernel_vmem"] = kv
    rec["kernel_vmem_ok"] = all(v["ok"] for v in kv.values())
    return rec


def run_cell(cell_name: str, mesh_kind: str, variant: str,
             save: bool = True):
    from .mesh import make_mesh, make_production_mesh
    if "chains4" in variant:
        # chains x shards fills the same chip count: a ("chain", 4)
        # axis carved out of the pod, rows sharded over the rest
        n = 512 if mesh_kind == "multi" else 256
        mesh = make_mesh((4, n // 4), ("chain", "data"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    cell = CELLS[cell_name]
    try:
        rec = lower_cell(cell, mesh, variant)
    except Exception as e:  # noqa: BLE001
        rec = {"arch": f"mf_{cell_name}", "shape": "gibbs_sweep",
               "mesh": mesh_kind, "variant": variant,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()}
    if save:
        RESULTS.mkdir(parents=True, exist_ok=True)
        tag = "" if variant == "baseline" else f".{variant}"
        out = RESULTS / f"mf_{cell_name}.gibbs_sweep.{mesh_kind}{tag}.json"
        out.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=list(CELLS) + ["all"], default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    # composable tags: build_model keys on the "bf16gather" substring,
    # lower_cell on "ring" — fail fast on anything else (a typo must
    # not lower 256 chips and write a baseline JSON under a bogus tag)
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "bf16gather", "ring",
                             "bf16gather_ring", "chains4",
                             "chains4_ring"])
    args = ap.parse_args()
    cells = list(CELLS) if args.cell == "all" else [args.cell]
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    fail = 0
    for c in cells:
        for mk in meshes:
            rec = run_cell(c, mk, args.variant)
            if "error" in rec:
                fail += 1
                print(f"{c:16s} {mk:6s} FAIL {rec['error'][:100]}")
            else:
                if not rec["contract_ok"]:
                    fail += 1
                ct = "ok" if rec["contract_ok"] else "CONTRACT-VIOLATED"
                print(f"{c:16s} {mk:6s} {ct} comp {rec['compute_s']:.2e} "
                      f"mem {rec['memory_s']:.2e} "
                      f"coll {rec['collective_s']:.2e} "
                      f"xchg {rec['exchange_s_modeled']:.2e} "
                      f"dom={rec['dominant']} rf={rec['roofline_fraction']:.4f}")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
