"""The main path compiled for a TPU v5e that is described, not attached.

Interpret mode on the CPU checks a kernel's arithmetic, not whether the
TPU compiler accepts it: block shapes, layouts and in-kernel ops that
interpret fine can still be refused.  These tests compile each Pallas
kernel of the main path (every ``ops.KERNELS`` probe, plus the widths
of the 1/32 ``bmf_chembl`` share that ``chip_smoke.py`` runs) and one
XLA Gibbs sweep at that share, for one chip of a v5e:2x2 topology.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.  Keep all such compiles in this one file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import AdaptiveGaussian
from repro.core.blocks import BlockDef, EntityDef, ModelDef
from repro.core.gibbs import MFData, gibbs_step, init_state
from repro.core.priors import NormalPrior
from repro.core.sparse import PaddedRows, SparseMatrix
from repro.kernels import ops
from repro.launch.roofline import peaks_for

KIND = "TPU v5 lite"
# the 1/32 row share of bmf_chembl: rows, cols, K, nnz per row, and the
# padded column width fixed_degree(0, ...) gives at that size
R, C, K, T, W = 32_768, 8_192, 128, 64, 328
PROBES = [(name, p)
          for name in ("gram", "sddmm", "topk_score", "chol_draw")
          for p in ops.KERNELS[name].probes]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's programs cannot be read back from the
    # persistent cache, so keep them out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == KIND
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Pallas calls compile for the chip even though this process's
    default backend is the CPU."""
    monkeypatch.setattr(ops, "_interpret", lambda interpret: False)


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding), tree)


def _kernel_hlo(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("name,probe", PROBES,
                         ids=[f"{n}-{p.label}" for n, p in PROBES])
def test_kernel_probe_compiles_for_v5e(name, probe, one_chip,
                                       compiled_kernels):
    assert "tpu_custom_call" in _kernel_hlo(
        probe.call, _on(one_chip, probe.args))


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("name,fn,shapes", [
    ("gram-rows", lambda v, x, m: ops.gram_and_rhs(v, x, m,
                                                   use_pallas=True),
     [(R, T, K), (R, T), (R, T)]),
    ("gram-cols", lambda v, x, m: ops.gram_and_rhs(v, x, m,
                                                   use_pallas=True),
     [(C, W, K), (C, W), (C, W)]),
    ("sddmm-test-set", lambda u, v: ops.sddmm(u, v, use_pallas=True),
     [(R * 8, K), (R * 8, K)]),
    ("topk-serving", lambda u, v, e: ops.topk_score(
        u, v, 100, exclude=e, use_pallas=True),
     [(8, 16, K), (16, C, K), (8, C)]),
], ids=lambda x: x if isinstance(x, str) else "")
def test_kernel_main_path_widths_compile_for_v5e(name, fn, shapes,
                                                 one_chip,
                                                 compiled_kernels):
    args = _on(one_chip, [_sds(s) for s in shapes])
    assert "tpu_custom_call" in _kernel_hlo(fn, args)


@pytest.fixture(scope="module")
def sweep_one_32nd_share(one_chip):
    """One Gibbs sweep of the 1/32 bmf_chembl share (the XLA path that
    ``Session`` runs by default), compiled for one chip."""
    model = ModelDef(
        (EntityDef("compound", R, NormalPrior(K)),
         EntityDef("protein", C, NormalPrior(K))),
        (BlockDef(0, 1, AdaptiveGaussian(), sparse=True),), K)
    E = R * T

    def padded(n, width, n_other):
        return PaddedRows(_sds((n, width), jnp.int32), _sds((n, width)),
                          _sds((n, width)), n_other=n_other)

    mat = SparseMatrix(padded(R, T, C), padded(C, W, R),
                       _sds((E,), jnp.int32), _sds((E,), jnp.int32),
                       _sds((E,)), _sds((E,)), _sds((E,), jnp.int32),
                       _sds((E,), jnp.int32), shape=(R, C))
    data = _on(one_chip, MFData((mat,), (None, None)))
    state = _on(one_chip, jax.eval_shape(lambda: init_state(model, data)))
    return gibbs_step.lower(model, data, state).compile()


def test_xla_sweep_one_32nd_share_fits_one_v5e(sweep_one_32nd_share):
    """The sweep fits one chip's HBM."""
    mem = sweep_one_32nd_share.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < peaks_for(KIND).hbm_bytes


def test_sweep_draws_factor_rows_with_the_kernel_on_v5e(
        sweep_one_32nd_share):
    """On the chip the factor draw is the ``chol_draw`` Mosaic kernel,
    once per entity, and no batched Cholesky or triangular inverse is
    left: the (K, K) ones of the hyper-parameter draws stay XLA's."""
    text = sweep_one_32nd_share.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and re.match(r"\s*%chol_draw(\.\d+)? = ", line)]
    assert len(kernels) == 2
    linalg = re.findall(
        r"= (f32\[[\d,]*\])\S* custom-call\([^\n]*custom_call_target="
        r'"(Cholesky|InvertDiagBlocksLowerTriangular)"', text)
    assert linalg, "the hyper-parameter draws' Cholesky calls are missing"
    for shape, target in linalg:
        dims = [int(d) for d in shape[4:-1].split(",")]
        assert math.prod(dims[:-2]) == 1, (target, shape)
