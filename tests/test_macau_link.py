"""The Macau link on the normal path: static side statistics built with
the data, the link's trace-time count, and lambda_beta's draw.

* ``gibbs_step`` on data built with its side statistics
  (``gibbs.make_data``) draws the chain that the side Gram computed
  inside the sweep draws;
* the single-device and the 8-device sharded Macau sweeps agree, and
  neither computes the side Gram or column sums in its sweep;
* ``link.direct_solves`` counts traced programs with the (D, D) link
  solve; data with side information but no statistics is refused;
* the lowered single-device sweep has no (D, D) product of the side
  matrix and no reduction of it over rows;
* lambda_beta's rate is tr(beta Lambda beta^T), formed without the
  (D, D) product.
"""
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AdaptiveGaussian, ModelBuilder, from_coo
from repro.core.gibbs import (MFData, gibbs_step, init_state,
                              side_statistics)
from repro.core.priors import MacauPrior
from repro.core.session import _place_step
from repro.obs import trace_counts

N_ROWS, N_COLS, K, D = 256, 64, 8, 24


def _model(n_rows=N_ROWS, d=D, seed=0):
    rng = np.random.default_rng(seed)
    i = np.repeat(np.arange(n_rows), 6)
    j = rng.integers(0, N_COLS, i.size)
    v = rng.normal(size=i.size).astype(np.float32)
    side = (rng.random((n_rows, d)) < 0.2).astype(np.float32)
    b = ModelBuilder(num_latent=K)
    b.add_entity("compound", n_rows, side_info=side)
    b.add_entity("protein", N_COLS)
    b.add_block("compound", "protein", from_coo(i, j, v, (n_rows, N_COLS)),
                noise=AdaptiveGaussian())
    model, data, _ = b.build()
    return model, data


def _chain(model, data, n=3, step=None):
    step = step or (lambda d, s: gibbs_step(model, d, s))
    state = init_state(model, data, seed=4)
    for _ in range(n):
        state, _ = step(data, state)
    return state


def _in_sweep(model):
    """The sweep with the side statistics formed inside the traced
    program, as a sweep without them at build would have to."""
    @jax.jit
    def step(data, state):
        side = data.sides[0]
        stats = ((side.T @ side, side.sum(axis=0)), None)
        return gibbs_step(model, data._replace(side_stats=stats), state)
    return step


def test_hoisted_side_statistics_keep_the_chain():
    model, data = _model()
    FtF, s = data.side_stats[0]
    side = np.asarray(data.sides[0])
    np.testing.assert_allclose(np.asarray(FtF), side.T @ side, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s), side.sum(0), rtol=1e-6)
    assert data.side_stats[1] is None
    a = _chain(model, data, step=_in_sweep(model))
    b = _chain(model, data)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)


def test_session_places_the_side_statistics():
    model, data = _model()
    placed, _, _ = _place_step(model, data, init_state(model, data), None,
                               None)
    assert placed is data
    assert placed.side_stats is not None and placed.side_stats[1] is None
    # without side information the builder's data carries no statistics
    b = ModelBuilder(num_latent=K)
    b.add_entity("compound", 16)
    b.add_entity("protein", 8)
    b.add_block("compound", "protein",
                from_coo([0, 1], [0, 1], [1.0, 2.0], (16, 8)))
    _, bdata, _ = b.build()
    assert bdata.side_stats == (None, None)


def test_link_trace_counts():
    # a row count of its own, so these programs are traced here
    model, data = _model(n_rows=264)
    state = init_state(model, data)
    before = trace_counts()
    gibbs_step.lower(model, data, state)
    after = trace_counts()
    assert after["link.direct_solves"] == \
        before.get("link.direct_solves", 0) + 1
    # side information without its statistics is refused, not formed in
    # the sweep
    bare = MFData(data.blocks, data.sides)
    with pytest.raises(ValueError, match="side statistics"):
        gibbs_step.lower(model, bare, state)


def _side_ops(text):
    """(D, D) dot products and row reductions of an (N, D) operand in a
    lowered module's StableHLO."""
    gram = re.findall(rf"dot_general.*-> tensor<{D}x{D}xf32>", text)
    colsum = re.findall(
        rf"stablehlo.reduce.*\(tensor<{N_ROWS}x{D}xf32>, tensor<f32>\) "
        rf"-> tensor<{D}xf32>", text)
    return gram, colsum


def test_lowered_sweep_has_no_side_gram_or_column_sums():
    model, data = _model()
    state = init_state(model, data)
    lowered = gibbs_step.lower(model, data, state)
    assert _side_ops(lowered.as_text()) == ([], [])
    # the detector sees them where the sweep does form them
    gram, colsum = _side_ops(_in_sweep(model).lower(data, state).as_text())
    assert gram and colsum
    # the link stage is named in the compiled program's op names
    assert "link/" in lowered.compile().as_text()


def test_side_statistics_of_abstract_sides():
    _, data = _model()
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            data)
    (FtF, s), none = side_statistics(abstract.sides)
    assert none is None
    assert FtF.shape == (D, D) and s.shape == (D,)


@pytest.mark.parametrize("sample", [True, False])
def test_link_precision_draw_is_the_trace_form(sample):
    prior = MacauPrior(K, 12, sample_beta_precision=sample)
    rng = np.random.default_rng(1)
    U = jnp.asarray(rng.normal(size=(40, K)), jnp.float32)
    side = jnp.asarray(rng.random((40, 12)) < 0.3, jnp.float32)
    hyper = prior.init(jax.random.PRNGKey(0), 40)
    key = jax.random.PRNGKey(5)
    h = prior.sample_hyper(key, U, hyper, side,
                           (side.T @ side, side.sum(axis=0)))
    if not sample:
        assert float(h["beta_prec"]) == prior.beta_precision
        return
    beta, Lam = h["beta"], h["Lambda"]
    old = jnp.trace(beta @ Lam @ beta.T)
    new = jnp.sum((beta @ Lam) * beta)
    np.testing.assert_allclose(float(new), float(old), rtol=1e-5)
    _, _, k_prec = jax.random.split(key, 3)
    a = 0.5 * beta.size + 1.0
    want = jax.random.gamma(k_prec, a) / (0.5 * old + 1.0)
    np.testing.assert_allclose(float(h["beta_prec"]), float(want),
                               rtol=1e-5)


_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import re
    import sys
    import numpy as np
    import jax
    sys.path.insert(0, %(tests)r)
    from test_macau_link import _model
    from repro.core.distributed import (distributed_supported,
                                        make_distributed_step)
    from repro.core.gibbs import gibbs_step, init_state
    from repro.launch.mesh import make_mesh
    from repro.obs import trace_counts

    model, data = _model()
    state = init_state(model, data, seed=4)
    st1 = state
    for _ in range(3):
        st1, m1 = gibbs_step(model, data, st1)
    mesh = make_mesh((8,), ("data",))
    assert distributed_supported(model, mesh, data)
    step, ds, ss = make_distributed_step(model, mesh, data, state)
    st2 = jax.device_put(state, ss)
    pdata = jax.device_put(data, ds)
    for _ in range(3):
        st2, m2 = step(pdata, st2)
    for a, b in zip(jax.tree.leaves(st1), jax.tree.leaves(st2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    counts = trace_counts()
    assert counts["link.direct_solves"] == 2, counts
    # no shard forms the side Gram or reduces its side rows: the
    # statistics come replicated with the data
    text = step.lower(pdata, st2).as_text()
    D = data.sides[0].shape[1]
    assert not re.findall(rf"dot_general.*-> tensor<{D}x{D}xf32>", text)
    assert not re.findall(
        rf"stablehlo.reduce.*\\(tensor<\\d+x{D}xf32>, tensor<f32>\\) "
        rf"-> tensor<{D}xf32>", text)
    print("OK")
""")


def test_single_device_and_sharded_macau_sweeps_agree():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    script = _SHARDED % {"tests": os.path.dirname(os.path.abspath(__file__))}
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout
