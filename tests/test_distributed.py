"""Distributed MF: the explicit shard_map Gibbs sweep on an 8-device
host mesh matches the single-device chain, and its compiled program
moves exactly one fixed-factor all-gather per half-sweep (eager
pipeline) or exactly ``n_shards - 1`` collective-permutes and ZERO
all-gathers per half-sweep (ring pipeline).

Ring contract (see core/distributed.py): the ring reassembles or
chunk-consumes the same bytes the all-gather moves, through pure data
movement (where/`dynamic_update_slice`) for every gather-indexed
consumer — so on sparse paths (gaussian, probit, macau, sparse-SnS)
the ring chain is BITWISE the eager chain, metrics included, asserted
below.  Dense blocks chunk-accumulate their Gram/RHS moments into the
circulating hops (the overlap that motivates the ring), which
reorders f32 summation: those chains are asserted at the same 2e-4 /
reduction-order tolerance as the distributed-vs-single-device
contract.

Agreement contract (see core/distributed.py): every per-row normal
draw is bit-identical to the single-device sweep (counter-based
``row_normals`` — asserted bitwise here), so the chains differ only by
reduction-order ULPs (K/K^2 moment psums, XLA batch-tiling of the
per-row solves) — asserted at 2e-4 over 3 sweeps, an order of
magnitude under a Gibbs chain's own step-to-step movement.

The same contract and tolerance cover the widened sharded subset:
probit noise (counter-based ``row_uniforms`` truncated-normal
augmentation), dense blocks (row-sharded stored orientations), and
spike-and-slab priors (counter-based ``row_bernoulli`` inclusions +
per-component-folded slab normals — the GFA composition), and the HLO
checks pin one fixed-factor all-gather per half-sweep for those paths
too, plus ZERO per-sweep Macau ``FtF`` psums (the (D, D) side-Gramian
is static data, built with the data) and ZERO per-component SnS collectives
(two K-sized hyper psums per view are the entire SnS budget).

All HLO pins are expressed through ``repro.analysis.contract``: each
script derives a ``CommContract`` with ``contract_for(model,
mesh_shape, pipeline)`` and verifies StableHLO + compiled HLO with
``assert_contract`` — no per-script collective regexes.

Runs in subprocesses because the device count must be set before jax
initializes (the main pytest process keeps the default 1 CPU device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import (FixedGaussian, MFData, init_state,
                            gibbs_step)
    from repro.core.blocks import BlockDef, EntityDef, ModelDef
    from repro.core.distributed import (distributed_supported,
                                        make_distributed_step,
                                        pad_rows_to)
    from repro.core.gibbs import row_normals
    from repro.core.priors import NormalPrior
    from repro.core.sparse import random_sparse
    from repro.launch.mesh import make_mesh

    # the mechanism: shard draws are bitwise slices of the global draws
    key = jax.random.PRNGKey(3)
    full = np.asarray(jax.jit(lambda: row_normals(key, 96, 8, 0))())
    for s in range(8):
        part = np.asarray(jax.jit(
            lambda s=s: row_normals(key, 12, 8, jnp.int32(12 * s)))())
        assert np.array_equal(part, full[12 * s:12 * (s + 1)]), s
    print("row draws bitwise")

    K = 8
    n_rows = pad_rows_to(96, 8)
    n_cols = pad_rows_to(48, 8)
    mat, test, _ = random_sparse(0, (n_rows, n_cols), 0.2, rank=4)
    model = ModelDef(
        (EntityDef("rows", n_rows, NormalPrior(K)),
         EntityDef("cols", n_cols, NormalPrior(K))),
        (BlockDef(0, 1, FixedGaussian(5.0), sparse=True),), K, False)
    data = MFData((mat,), (None, None))
    state = init_state(model, data, seed=0)

    # single-device chain
    st1 = state
    for _ in range(3):
        st1, m1 = gibbs_step(model, data, st1)

    # 8-device explicit shard_map chain
    mesh = make_mesh((4, 2), ("data", "model"))
    assert distributed_supported(model, mesh, data)
    step, ds, ss = make_distributed_step(model, mesh, data, state)
    pdata = jax.device_put(data, ds)
    pstate = jax.device_put(state, ss)
    st2 = pstate
    for _ in range(3):
        st2, m2 = step(pdata, st2)

    for a, b in zip(st1.factors, st2.factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    print("rmse", float(m1["rmse_train_0"]), float(m2["rmse_train_0"]))
    np.testing.assert_allclose(float(m1["rmse_train_0"]),
                               float(m2["rmse_train_0"]), rtol=1e-3)

    # elastic shrink: 8 -> 6 devices, same chain continues
    mesh2 = make_mesh((6,), ("data",))
    assert distributed_supported(model, mesh2, data)
    step2, ds2, ss2 = make_distributed_step(model, mesh2, data, state)
    st3 = jax.device_put(st2, ss2)
    d3 = jax.device_put(data, ds2)
    st3, m3 = step2(d3, st3)
    st1b, m1b = gibbs_step(model, data, st1)
    np.testing.assert_allclose(float(m1b["rmse_train_0"]),
                               float(m3["rmse_train_0"]), rtol=1e-3)
    print("OK")
""")

_WIDENED_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import (FixedGaussian, MFData, ProbitNoise,
                            dense_block, init_state, gibbs_step)
    from repro.core.blocks import BlockDef, EntityDef, ModelDef
    from repro.core.gibbs import row_uniforms
    from repro.core.distributed import (distributed_supported,
                                        make_distributed_step)
    from repro.core.priors import NormalPrior
    from repro.core.sparse import random_sparse
    from repro.launch.mesh import make_mesh

    # the mechanism: probit's truncated-normal uniforms are bitwise
    # shard slices, same contract as row_normals
    key = jax.random.PRNGKey(5)
    full = np.asarray(jax.jit(lambda: row_uniforms(key, 96, 16, 0))())
    for s in range(8):
        part = np.asarray(jax.jit(
            lambda s=s: row_uniforms(key, 12, 16, jnp.int32(12 * s)))())
        assert np.array_equal(part, full[12 * s:12 * (s + 1)]), s
    print("row uniforms bitwise")

    K = 8
    n_rows, n_cols = 96, 48
    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)

    def two_entity(noise, sparse):
        return ModelDef((EntityDef("r", n_rows, NormalPrior(K)),
                         EntityDef("c", n_cols, NormalPrior(K))),
                        (BlockDef(0, 1, noise, sparse=sparse),), K,
                        False)

    def parity(name, model, data):
        state = init_state(model, data, seed=0)
        st1 = state
        for _ in range(3):
            st1, m1 = gibbs_step(model, data, st1)
        assert distributed_supported(model, mesh, data), name
        step, ds, ss = make_distributed_step(model, mesh, data, state)
        st2 = jax.device_put(state, ss)
        pdata = jax.device_put(data, ds)
        for _ in range(3):
            st2, m2 = step(pdata, st2)
        for a, b in zip(st1.factors, st2.factors):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(float(m1["rmse_train_0"]),
                                   float(m2["rmse_train_0"]), rtol=1e-3)
        print(name, "parity ok", float(m2["rmse_train_0"]))

    # probit on sparse binary data (compound-activity classification)
    bmat, _, _ = random_sparse(0, (n_rows, n_cols), 0.2, rank=4,
                               binary=True)
    parity("probit", two_entity(ProbitNoise(), True),
           MFData((bmat,), (None, None)))

    # fully-observed dense block (shared-Gram path)
    R = rng.normal(size=(n_rows, n_cols)).astype(np.float32)
    parity("dense_full", two_entity(FixedGaussian(5.0), False),
           MFData((dense_block(R),), (None, None)))

    # masked dense block under probit (per-row-Gram path + augmentation)
    Xb = (R > 0).astype(np.float32)
    m = (rng.random((n_rows, n_cols)) < 0.6).astype(np.float32)
    parity("dense_masked_probit", two_entity(ProbitNoise(), False),
           MFData((dense_block(Xb, mask=m),), (None, None)))
    print("OK")
""")

_SNS_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import (AdaptiveGaussian, FixedGaussian, MFData,
                            dense_block, init_state, gibbs_step)
    from repro.core.blocks import BlockDef, EntityDef, ModelDef
    from repro.core.gibbs import row_bernoulli
    from repro.core.distributed import (distributed_supported,
                                        make_distributed_step)
    from repro.core.priors import (FixedNormalPrior, NormalPrior,
                                   SpikeAndSlabPrior)
    from repro.core.sparse import random_sparse
    from repro.launch.mesh import make_mesh

    # the mechanism: SnS inclusion draws are bitwise shard slices,
    # the same counter-based contract as row_normals/row_uniforms
    key = jax.random.PRNGKey(7)
    p = jnp.asarray(np.random.default_rng(0).random(96), jnp.float32)
    full = np.asarray(jax.jit(lambda: row_bernoulli(key, p, 0))())
    for s in range(8):
        part = np.asarray(jax.jit(
            lambda s=s: row_bernoulli(key, p[12 * s:12 * (s + 1)],
                                      jnp.int32(12 * s)))())
        assert np.array_equal(part, full[12 * s:12 * (s + 1)]), s
    print("row bernoulli bitwise")

    K = 4
    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)

    def parity(name, model, data, check_sns_hypers):
        state = init_state(model, data, seed=0)
        st1 = state
        for _ in range(3):
            st1, m1 = gibbs_step(model, data, st1)
        assert distributed_supported(model, mesh, data), name
        step, ds, ss = make_distributed_step(model, mesh, data, state)
        st2 = jax.device_put(state, ss)
        pdata = jax.device_put(data, ds)
        for _ in range(3):
            st2, m2 = step(pdata, st2)
        for a, b in zip(st1.factors, st2.factors):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(float(m1["rmse_train_0"]),
                                   float(m2["rmse_train_0"]), rtol=1e-3)
        # the replicated rho/tau hyper-state is the single-device one
        for e in check_sns_hypers:
            for hk in ("rho", "tau"):
                np.testing.assert_allclose(
                    np.asarray(st1.hypers[e][hk]),
                    np.asarray(st2.hypers[e][hk]), rtol=2e-3, atol=2e-3)
        print(name, "parity ok", float(m2["rmse_train_0"]))
        return st2

    # the GFA composition (paper Table 1 "Normal + SnS"): shared Z
    # against 3 dense views, spike-and-slab loadings; every dim
    # divides BOTH the 8-device mesh and the 6-survivor re-mesh
    N, dims = 96, (72, 48, 24)
    Z = rng.normal(size=(N, K)).astype(np.float32)
    ents = [EntityDef("samples", N, FixedNormalPrior(K))]
    blocks, payloads = [], []
    for m, D in enumerate(dims):
        W = rng.normal(size=(D, K)).astype(np.float32)
        X = (Z @ W.T + 0.1 * rng.normal(size=(N, D))).astype(np.float32)
        ents.append(EntityDef(f"view{m}", D, SpikeAndSlabPrior(K)))
        blocks.append(BlockDef(0, m + 1, AdaptiveGaussian(),
                               sparse=False))
        payloads.append(dense_block(X))
    gfa_model = ModelDef(tuple(ents), tuple(blocks), K, False)
    gfa_data = MFData(tuple(payloads), tuple([None] * len(ents)))
    st = parity("gfa", gfa_model, gfa_data,
                check_sns_hypers=range(1, len(ents)))

    # elastic shrink carrying the rho/tau hyper-state: 8 -> 6 devices
    mesh6 = make_mesh((6,), ("data",))
    assert distributed_supported(gfa_model, mesh6, gfa_data)
    state0 = init_state(gfa_model, gfa_data, seed=0)
    step6, ds6, ss6 = make_distributed_step(gfa_model, mesh6, gfa_data,
                                            state0)
    st6, m6 = step6(jax.device_put(gfa_data, ds6),
                    jax.device_put(st, ss6))
    ref = state0
    for _ in range(4):
        ref, mref = gibbs_step(gfa_model, gfa_data, ref)
    np.testing.assert_allclose(float(mref["rmse_train_0"]),
                               float(m6["rmse_train_0"]), rtol=1e-3)
    print("gfa elastic remesh ok")

    # SnS on one axis of a sparse block (BMF + SnS, Table 1)
    smat, _, _ = random_sparse(0, (96, 48), 0.2, rank=4)
    sns_model = ModelDef(
        (EntityDef("r", 96, NormalPrior(K)),
         EntityDef("c", 48, SpikeAndSlabPrior(K))),
        (BlockDef(0, 1, FixedGaussian(5.0), sparse=True),), K, False)
    parity("sparse_sns", sns_model, MFData((smat,), (None, None)),
           check_sns_hypers=(1,))
    print("OK")
""")

_HLO_SNS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax

    from repro.analysis.contract import assert_contract, contract_for
    from repro.core import (AdaptiveGaussian, MFData, dense_block,
                            init_state)
    from repro.core.blocks import BlockDef, EntityDef, ModelDef
    from repro.core.distributed import (distributed_supported,
                                        make_distributed_step)
    from repro.core.priors import FixedNormalPrior, SpikeAndSlabPrior
    from repro.launch.mesh import make_mesh

    K = 8
    N, dims = 96, (48, 24)
    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    ents = [EntityDef("samples", N, FixedNormalPrior(K))]
    blocks, payloads = [], []
    for m, D in enumerate(dims):
        X = rng.normal(size=(N, D)).astype(np.float32)
        ents.append(EntityDef(f"view{m}", D, SpikeAndSlabPrior(K)))
        blocks.append(BlockDef(0, m + 1, AdaptiveGaussian(),
                               sparse=False))
        payloads.append(dense_block(X))
    model = ModelDef(tuple(ents), tuple(blocks), K, False)
    data = MFData(tuple(payloads), tuple([None] * len(ents)))
    assert distributed_supported(model, mesh, data)
    state = init_state(model, data, seed=0)
    # the EAGER exchange contract is pinned explicitly (the ring
    # pipeline has its own HLO script and the env default may be ring)
    step, ds, ss = make_distributed_step(model, mesh, data, state,
                                         pipeline="eager")
    lowered = step.lower(data, state)

    # the derived contract IS the old hand-pins: one fixed-factor
    # all-gather per half-sweep (E entities -> E gathers); hyper/noise
    # psums only — 2 K-sized SnS moments per view + 2 scalar SSE/nnz
    # per block, so 4 per view and ZERO per-component collectives
    # (the K-unrolled coordinate loop would add ~K more each); and
    # every backend all-reduce payload at most K-sized (the gathered
    # factors are consumed, not reduced)
    M = len(dims)
    c = contract_for(model, (8,), "eager")
    assert c.all_gathers == len(model.entities), c
    assert c.all_reduces == 4 * M, c
    assert c.max_reduce_elems == K, c
    assert_contract(c, lowered_text=lowered.as_text(),
                    compiled_text=lowered.compile().as_text(),
                    where="gfa/eager")
    print("all-gathers", c.all_gathers, "all-reduces", c.all_reduces)
    print("OK")
""")

_RING_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import (AdaptiveGaussian, FixedGaussian, MFData,
                            ProbitNoise, dense_block, init_state,
                            gibbs_step)
    from repro.core.blocks import BlockDef, EntityDef, ModelDef
    from repro.core import distributed as D
    from repro.core.priors import (FixedNormalPrior, MacauPrior,
                                   NormalPrior, SpikeAndSlabPrior)
    from repro.core.sparse import random_sparse
    from repro.core.gibbs import make_data
    from repro.launch.mesh import make_mesh

    K = 8
    n_rows, n_cols = 96, 48
    # the flattened two-axis mesh: the ring permutes over ("data",
    # "model") jointly, the hardest routing case
    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)

    def run(model, data, pipeline, sweeps=3):
        state = init_state(model, data, seed=0)
        assert D.distributed_supported(model, mesh, data)
        step, ds, ss = D.make_distributed_step(model, mesh, data, state,
                                               pipeline=pipeline)
        st = jax.device_put(state, ss)
        pdata = jax.device_put(data, ds)
        for _ in range(sweeps):
            st, m = step(pdata, st)
        return st, m

    def parity(name, model, data, bitwise):
        st1 = init_state(model, data, seed=0)
        for _ in range(3):
            st1, m1 = gibbs_step(model, data, st1)
        ste, me = run(model, data, "eager")
        str_, mr = run(model, data, "ring")
        # ring matches the single-device chain at the distributed
        # contract tolerance for every family...
        for a, b in zip(st1.factors, str_.factors):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)
        # ...and matches the eager sharded chain BITWISE on sparse
        # paths (the ring reassembles the exact gather operands:
        # data movement only, no re-summation), metrics included
        for a, b in zip(ste.factors, str_.factors):
            if bitwise:
                assert np.array_equal(np.asarray(a), np.asarray(b)), name
            else:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-4, atol=2e-4,
                                           err_msg=name)
        for k in me:
            if bitwise:
                assert float(me[k]) == float(mr[k]), (name, k)
            else:
                np.testing.assert_allclose(float(me[k]), float(mr[k]),
                                           rtol=1e-4, err_msg=(name, k))
        print(name, "ring parity ok", "bitwise" if bitwise else "2e-4",
              float(mr["rmse_train_0"]))

    def two_entity(noise, sparse, row_prior=None):
        return ModelDef(
            (EntityDef("r", n_rows, row_prior or NormalPrior(K)),
             EntityDef("c", n_cols, NormalPrior(K))),
            (BlockDef(0, 1, noise, sparse=sparse),), K, False)

    mat, _, _ = random_sparse(0, (n_rows, n_cols), 0.2, rank=4)
    parity("gaussian", two_entity(FixedGaussian(5.0), True),
           MFData((mat,), (None, None)), bitwise=True)

    bmat, _, _ = random_sparse(0, (n_rows, n_cols), 0.2, rank=4,
                               binary=True)
    parity("probit", two_entity(ProbitNoise(), True),
           MFData((bmat,), (None, None)), bitwise=True)

    Dside = 12
    side = jnp.asarray(rng.normal(size=(n_rows, Dside)), jnp.float32)
    parity("macau",
           two_entity(FixedGaussian(5.0), True,
                      row_prior=MacauPrior(K, Dside)),
           make_data((mat,), (side, None)), bitwise=True)

    # dense blocks chunk-accumulate their moments into the ring hops
    # (the overlap), which reorders the f32 sums -> 2e-4, not bitwise
    R = rng.normal(size=(n_rows, n_cols)).astype(np.float32)
    parity("dense_full", two_entity(FixedGaussian(5.0), False),
           MFData((dense_block(R),), (None, None)), bitwise=False)

    # the GFA composition: FixedNormal Z + SnS loadings on 3 views
    N, dims = 96, (72, 48, 24)
    Z = rng.normal(size=(N, K)).astype(np.float32)
    ents = [EntityDef("samples", N, FixedNormalPrior(K))]
    blocks, payloads = [], []
    for m, Dm in enumerate(dims):
        W = rng.normal(size=(Dm, K)).astype(np.float32)
        X = (Z @ W.T + 0.1 * rng.normal(size=(N, Dm))).astype(np.float32)
        ents.append(EntityDef(f"view{m}", Dm, SpikeAndSlabPrior(K)))
        blocks.append(BlockDef(0, m + 1, AdaptiveGaussian(),
                               sparse=False))
        payloads.append(dense_block(X))
    parity("gfa", ModelDef(tuple(ents), tuple(blocks), K, False),
           MFData(tuple(payloads), tuple([None] * len(ents))),
           bitwise=False)

    # SnS on the sparse block's column axis (BMF + SnS): the SnS
    # coordinate loop reads the ring-reassembled view -> bitwise
    parity("sparse_sns",
           ModelDef((EntityDef("r", n_rows, NormalPrior(K)),
                     EntityDef("c", n_cols, SpikeAndSlabPrior(K))),
                    (BlockDef(0, 1, FixedGaussian(5.0), sparse=True),),
                    K, False),
           MFData((mat,), (None, None)), bitwise=True)

    # the scan-rolled ring (production shard counts): force the rolled
    # form on this 8-device mesh and pin it to the same bitwise chain
    D.RING_UNROLL_MAX = 4
    ste, me = run(two_entity(FixedGaussian(5.0), True),
                  MFData((mat,), (None, None)), "eager")
    str_, mr = run(two_entity(FixedGaussian(5.0), True),
                   MFData((mat,), (None, None)), "ring")
    for a, b in zip(ste.factors, str_.factors):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "scan ring"
    print("scan-rolled ring bitwise ok")
    print("OK")
""")

_RING_HLO_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.analysis.contract import assert_contract, contract_for
    from repro.core import (AdaptiveGaussian, FixedGaussian, MFData,
                            ProbitNoise, dense_block, init_state)
    from repro.core.blocks import BlockDef, EntityDef, ModelDef
    from repro.core.distributed import (distributed_supported,
                                        make_distributed_step)
    from repro.core.priors import (FixedNormalPrior, MacauPrior,
                                   NormalPrior, SpikeAndSlabPrior)
    from repro.core.sparse import random_sparse
    from repro.core.gibbs import make_data
    from repro.launch.mesh import make_mesh

    K, Dside = 8, 12
    n_rows, n_cols = 96, 48
    S = 8
    rng = np.random.default_rng(0)
    mat, _, _ = random_sparse(0, (n_rows, n_cols), 0.2, rank=4)
    bmat, _, _ = random_sparse(0, (n_rows, n_cols), 0.2, rank=4,
                               binary=True)
    R = rng.normal(size=(n_rows, n_cols)).astype(np.float32)
    side = jnp.asarray(rng.normal(size=(n_rows, Dside)), jnp.float32)

    def ents(row_prior):
        return (EntityDef("r", n_rows, row_prior),
                EntityDef("c", n_cols, NormalPrior(K)))

    gfa_ents = [EntityDef("samples", 96, FixedNormalPrior(K)),
                EntityDef("view0", 48, SpikeAndSlabPrior(K)),
                EntityDef("view1", 24, SpikeAndSlabPrior(K))]
    gfa_blocks = [BlockDef(0, 1, AdaptiveGaussian(), sparse=False),
                  BlockDef(0, 2, AdaptiveGaussian(), sparse=False)]
    gfa_payloads = tuple(
        dense_block(rng.normal(size=(96, Dm)).astype(np.float32))
        for Dm in (48, 24))

    cases = {
        "gaussian": (
            ModelDef(ents(NormalPrior(K)),
                     (BlockDef(0, 1, FixedGaussian(5.0), sparse=True),),
                     K),
            MFData((mat,), (None, None))),
        "gaussian_bf16": (
            ModelDef(ents(NormalPrior(K)),
                     (BlockDef(0, 1, FixedGaussian(5.0), sparse=True),),
                     K, use_pallas=False, bf16_gather=True),
            MFData((mat,), (None, None))),
        "probit": (
            ModelDef(ents(NormalPrior(K)),
                     (BlockDef(0, 1, ProbitNoise(), sparse=True),), K),
            MFData((bmat,), (None, None))),
        "macau": (
            ModelDef(ents(MacauPrior(K, Dside)),
                     (BlockDef(0, 1, FixedGaussian(5.0), sparse=True),),
                     K),
            make_data((mat,), (side, None))),
        "dense_full": (
            ModelDef(ents(NormalPrior(K)),
                     (BlockDef(0, 1, FixedGaussian(5.0), sparse=False),),
                     K),
            MFData((dense_block(R),), (None, None))),
        "gfa": (
            ModelDef(tuple(gfa_ents), tuple(gfa_blocks), K, False),
            MFData(gfa_payloads, (None, None, None))),
    }

    # both mesh layouts: single axis and the flattened two-axis ring
    for mesh_shape, mesh_axes in (((8,), ("data",)),
                                  ((4, 2), ("data", "model"))):
        mesh = make_mesh(mesh_shape, mesh_axes)
        for name, (model, data) in cases.items():
            assert distributed_supported(model, mesh, data), name
            state = init_state(model, data, seed=0)
            step, ds, ss = make_distributed_step(model, mesh, data,
                                                 state, pipeline="ring")
            lowered = step.lower(data, state)
            E = len(model.entities)

            # the ring communication contract, derived not hand-pinned:
            # ZERO full-factor all-gathers anywhere in the program and
            # exactly n_shards - 1 collective-permutes per half-sweep
            # (one circulation per entity per sweep — the metrics
            # reuse the final half-sweep's reassembled view, exactly
            # like eager reuses its gather), bf16 on the wire when the
            # model flags it; checked on StableHLO AND the backend
            c = contract_for(model, mesh_shape, "ring")
            assert c.all_gathers == 0, c
            assert c.collective_permutes == E * (S - 1), c
            assert c.wire_dtype == \\
                ("bf16" if model.bf16_gather else "f32"), c
            assert_contract(c, lowered_text=lowered.as_text(),
                            compiled_text=lowered.compile().as_text(),
                            where=f"{name}/{mesh_shape}/ring")
            print(name, "x".join(map(str, mesh_shape)),
                  "collective-permutes", c.collective_permutes,
                  "all-gathers 0")
    print("OK")
""")

_HLO_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax

    from repro.analysis.contract import assert_contract, contract_for
    from repro.core import FixedGaussian, MFData, init_state
    from repro.core.blocks import BlockDef, EntityDef, ModelDef
    from repro.core.distributed import make_distributed_step
    from repro.core.priors import NormalPrior
    from repro.core.sparse import random_sparse
    from repro.launch.mesh import make_mesh

    mat, _, _ = random_sparse(0, (96, 48), 0.2, rank=4)
    data = MFData((mat,), (None, None))
    mesh = make_mesh((8,), ("data",))

    for bf16 in (False, True):
        model = ModelDef(
            (EntityDef("rows", 96, NormalPrior(8)),
             EntityDef("cols", 48, NormalPrior(8))),
            (BlockDef(0, 1, FixedGaussian(5.0), sparse=True),), 8,
            use_pallas=False, bf16_gather=bf16)
        state = init_state(model, data, seed=0)
        step, ds, ss = make_distributed_step(model, mesh, data, state,
                                             pipeline="eager")
        lowered = step.lower(data, state)

        # the communication contract, derived from the ModelDef: one
        # all-gather of the fixed factor per half-sweep (2 entities ->
        # exactly 2), carried in bf16 when the model flags it — checked
        # on StableHLO and on the backend (XLA:CPU normalizes bf16
        # collectives to convert-gather-convert but must not duplicate
        # or split them)
        c = contract_for(model, (8,), "eager")
        assert c.all_gathers == len(model.entities), c
        assert c.wire_dtype == ("bf16" if bf16 else "f32"), c
        assert_contract(c, lowered_text=lowered.as_text(),
                        compiled_text=lowered.compile().as_text(),
                        where="bf16" if bf16 else "f32")
        print("variant", "bf16" if bf16 else "f32",
              "all-gathers", c.all_gathers)
    print("OK")
""")

_HLO_WIDENED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.analysis.contract import assert_contract, contract_for
    from repro.core import (FixedGaussian, MFData, ProbitNoise,
                            dense_block, init_state)
    from repro.core.blocks import BlockDef, EntityDef, ModelDef
    from repro.core.distributed import (distributed_supported,
                                        make_distributed_step)
    from repro.core.priors import MacauPrior, NormalPrior
    from repro.core.sparse import random_sparse
    from repro.core.gibbs import make_data
    from repro.launch.mesh import make_mesh

    K, D = 8, 12          # D != K so the FtF shape is unambiguous
    n_rows, n_cols = 96, 48
    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    bmat, _, _ = random_sparse(0, (n_rows, n_cols), 0.2, rank=4,
                               binary=True)
    smat, _, _ = random_sparse(1, (n_rows, n_cols), 0.2, rank=4)
    R = rng.normal(size=(n_rows, n_cols)).astype(np.float32)
    side = jnp.asarray(rng.normal(size=(n_rows, D)).astype(np.float32))

    def ents(row_prior):
        return (EntityDef("r", n_rows, row_prior),
                EntityDef("c", n_cols, NormalPrior(K)))

    cases = {
        "probit_sparse": (
            ModelDef(ents(NormalPrior(K)),
                     (BlockDef(0, 1, ProbitNoise(), sparse=True),), K),
            MFData((bmat,), (None, None))),
        "probit_sparse_bf16": (
            ModelDef(ents(NormalPrior(K)),
                     (BlockDef(0, 1, ProbitNoise(), sparse=True),), K,
                     use_pallas=False, bf16_gather=True),
            MFData((bmat,), (None, None))),
        "dense_full": (
            ModelDef(ents(NormalPrior(K)),
                     (BlockDef(0, 1, FixedGaussian(5.0), sparse=False),),
                     K),
            MFData((dense_block(R),), (None, None))),
        "macau": (
            ModelDef(ents(MacauPrior(K, D)),
                     (BlockDef(0, 1, FixedGaussian(5.0), sparse=True),),
                     K),
            make_data((smat,), (side, None))),
    }

    for name, (model, data) in cases.items():
        assert distributed_supported(model, mesh, data), name
        state = init_state(model, data, seed=0)
        step, ds, ss = make_distributed_step(model, mesh, data, state,
                                             pipeline="eager")
        lowered = step.lower(data, state)

        # communication contract, derived from the ModelDef: ONE
        # all-gather of the fixed factor per half-sweep, bf16 on the
        # wire when flagged.  The Macau FtF hoist is subsumed by the
        # payload bound: the contract's max all-reduce payload
        # (max(K^2, D*K) for Macau) is strictly below the D*D
        # side-Gramian, so a per-sweep FtF psum would violate it.
        c = contract_for(model, (8,), "eager")
        assert c.all_gathers == len(model.entities), (name, c)
        assert c.wire_dtype == \\
            ("bf16" if model.bf16_gather else "f32"), (name, c)
        if name == "macau":
            assert c.max_reduce_elems == max(K * K, D * K) < D * D, c
        assert_contract(c, lowered_text=lowered.as_text(),
                        compiled_text=lowered.compile().as_text(),
                        where=name)
        print(name, "all-gathers", c.all_gathers,
              "max psum elems", c.max_reduce_elems)
    print("OK")
""")


# The same parity with the factor draw on the chol_draw kernel's branch
# (interpret mode): the sharded sweep draws each shard's rows with the
# kernel, the single device all rows at once.
_KERNEL_PARITY_SCRIPT = _PARITY_SCRIPT.replace(
    "import jax.numpy as jnp\n", textwrap.dedent("""\
    import jax.numpy as jnp
    import functools
    from repro.kernels import ops
    from repro.obs import trace_counts
    ops._draw_elsewhere = functools.partial(ops.chol_draw, interpret=True)
    """), 1).replace('print("OK")', textwrap.dedent("""\
    assert trace_counts().get("solve.kernel_draws", 0) >= 6, trace_counts()
    assert "solve.lax_draws" not in trace_counts()
    print("OK")"""))


def _run(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


def test_resolve_pipeline_validates_choices(monkeypatch):
    """The pipeline knob fails fast with the valid choices (the
    ``_PRIORS`` ValueError contract) and defers to REPRO_PIPELINE —
    the env hook the CI ring leg rides — only when unset."""
    from repro.core.distributed import resolve_pipeline

    monkeypatch.delenv("REPRO_PIPELINE", raising=False)
    assert resolve_pipeline() == "eager"
    assert resolve_pipeline("ring") == "ring"
    monkeypatch.setenv("REPRO_PIPELINE", "ring")
    assert resolve_pipeline() == "ring"
    assert resolve_pipeline("eager") == "eager"   # explicit wins
    with pytest.raises(ValueError, match="valid pipelines.*eager.*ring"):
        resolve_pipeline("warp")
    monkeypatch.setenv("REPRO_PIPELINE", "warp")
    with pytest.raises(ValueError, match="REPRO_PIPELINE"):
        resolve_pipeline()


@pytest.mark.slow
def test_distributed_gibbs_matches_single_device():
    _run(_PARITY_SCRIPT)


@pytest.mark.slow
def test_distributed_kernel_draw_matches_single_device():
    """The sharded sweep against the single device with every factor
    draw on the kernel's branch, at the lax path's 2e-4 parity."""
    assert "ops._draw_elsewhere" in _KERNEL_PARITY_SCRIPT
    _run(_KERNEL_PARITY_SCRIPT)


@pytest.mark.slow
def test_distributed_widened_subset_matches_single_device():
    """Probit noise + dense blocks ride the explicit sweep at the
    same 2e-4 parity as the Gaussian sparse path."""
    _run(_WIDENED_PARITY_SCRIPT)


@pytest.mark.slow
def test_distributed_hlo_one_allgather_per_halfsweep():
    _run(_HLO_SCRIPT)


@pytest.mark.slow
def test_distributed_hlo_widened_paths_and_ftf_hoist():
    """One all-gather per half-sweep holds for probit/dense/Macau, and
    the Macau side-Gramian psum is gone from the per-sweep program."""
    _run(_HLO_WIDENED_SCRIPT)


@pytest.mark.slow
def test_distributed_sns_gfa_matches_single_device():
    """Spike-and-slab (GFA multi-view + sparse BMF+SnS) rides the
    explicit sweep at the same 2e-4 parity, carries replicated rho/tau
    hyper-state, and survives an 8 -> 6 re-mesh mid-chain."""
    _run(_SNS_PARITY_SCRIPT)


@pytest.mark.slow
def test_distributed_hlo_sns_collective_contract():
    """GFA HLO: one fixed-factor all-gather per half-sweep, exactly
    two K-sized hyper psums per SnS view plus the scalar noise psums,
    and ZERO per-component collectives."""
    _run(_HLO_SNS_SCRIPT)


@pytest.mark.slow
def test_distributed_ring_matches_eager():
    """The ring-pipelined sweep (S-1 double-buffered ppermute hops per
    half-sweep) matches the eager all-gather sweep: bitwise — metrics
    included — on every sparse path (gaussian, probit, macau,
    sparse-SnS), at the 2e-4 reduction-order tolerance on the
    chunk-accumulated dense/GFA paths, and within 2e-4 of the
    single-device chain for all of them.  Also pins the scan-rolled
    ring (production shard counts) to the same bitwise chain."""
    _run(_RING_PARITY_SCRIPT)


@pytest.mark.slow
def test_distributed_ring_hlo_collective_contract():
    """Ring HLO across the model zoo on both mesh layouts: exactly
    n_shards - 1 collective-permutes per half-sweep (one circulation
    per entity per sweep, bf16 on the wire when flagged) and ZERO
    full-factor all-gathers anywhere in the program."""
    _run(_RING_HLO_SCRIPT)
