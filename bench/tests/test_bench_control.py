"""The comparison that decides ``correct`` fails what it must.

* Each control, the reference computed in a lower precision in the
  program's place (all of it in bfloat16, or only a factor draw's
  Gram, Cholesky and solves), reads above at least one of each cell's
  limits (``bench/limits/<cell>.json``).
* A run whose timed path is broken underneath, past the harness's look
  for a chip, prints ``correct: false``: a sweep that returns its state
  unchanged, one that leaves half of its rows out of the update, one
  whose draw of one row is altered where it is produced, one that keeps
  the previous sweep's hyper-parameters, a sharded sweep without its
  exchange between chips, and a served answer altered where it is
  produced.

All at the tiny sizes of ``rehearse.TINY`` on the CPU.
"""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(str(tmp_path_factory.mktemp("bench")))


def _limits(root, cell):
    with open(os.path.join(root, "bench", "limits", cell + ".json")) as f:
        return json.load(f)


def _control(root, cell, seed):
    """The control's readings for one tiny run of ``cell``."""
    sys.path.insert(0, os.path.join(root, "bench"))
    try:
        from benchkit import harness
        bm = json.load(open(os.path.join(root, "BENCHMARK.json")))
        spec = next(c for c in bm["workloads"] if c["name"] == cell)
        run = harness.Run(root, bm, spec, seed, 0.3, False, 0.0)
        import jax
        run.devices = jax.devices()[:1]
        kind = harness.load_module(
            os.path.join(root, "bench", "traffic",
                         run.mix["kind"] + ".py"), "t_" + run.mix["kind"])
        kind.run(run)
        return run.correct, run.controls
    finally:
        sys.path.remove(os.path.join(root, "bench"))


@pytest.mark.parametrize("cell, control", [
    ("bmf_chembl.sweep", "bf16"), ("bmf_chembl.sweep", "bf16_solve"),
    ("bmf_chembl.recommend_batch", "bf16")])
@pytest.mark.parametrize("seed", [2**31 + 9])
def test_control_reads_above_a_limit(root, cell, control, seed):
    sound, controls = _control(root, cell, seed)
    assert sound
    readings = controls[control]()
    limits = _limits(root, cell)
    failed = [k for k in limits if readings[k] > limits[k]]
    assert failed, (readings, limits)


def _broken_step(kind):
    """A stand-in for the session's sweep with a fault planted in it."""
    import jax.numpy as jnp
    from repro.core import gibbs

    def step(model, data, state):
        new, metrics = gibbs.gibbs_step(model, data, state)
        if kind == "unchanged":
            return state, metrics
        if kind == "stale_hyper":
            return new._replace(hypers=state.hypers), metrics
        u = new.factors[0]
        if kind == "half_rows":
            n = u.shape[0] // 2
            u = u.at[:n].set(state.factors[0][:n])
        else:                                   # one row's draw altered
            u = u.at[3].set(-u[3])
        return new._replace(factors=(u,) + tuple(new.factors[1:])), metrics
    return step


@pytest.mark.parametrize("kind", ["unchanged", "half_rows", "one_row",
                                  "stale_hyper"])
def test_broken_sweep_is_not_correct(root, kind, monkeypatch):
    from repro.core import session
    monkeypatch.setattr(session, "gibbs_step", _broken_step(kind))
    rc, line, _ = rehearse.run_cell(root, "bmf_chembl.sweep", seconds=0.3)
    assert rc == 0
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_altered_answer_is_not_correct(root, monkeypatch):
    from repro.launch import serve
    real = serve.RecommendServer.step

    def step(self):
        live = [r for r in self.active if r is not None]
        real(self)
        for r in live:
            r["ids"] = r["ids"].copy()
            r["ids"][0] = r["exclude"][0]
    monkeypatch.setattr(serve.RecommendServer, "step", step)
    rc, line, _ = rehearse.run_cell(root, "bmf_chembl.recommend_batch",
                                    seconds=0.3)
    assert rc == 0
    assert line["correct"] is False
    assert line["checks"]["wrong_ids"]["value"] > 0


def test_sharded_sweep_without_exchange_is_not_correct(root):
    code = f"""
import json, sys
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
import jax, jax.numpy as jnp
import rehearse

def local_only(x, axis_name, *, axis=0, tiled=False, **kw):
    # the exchange left out: every chip sees its own shard, repeated
    return jnp.concatenate([x] * 4, axis=axis) if tiled else \\
        jnp.stack([x] * 4, axis=axis)

jax.lax.all_gather = local_only
rc, line, out = rehearse.run_cell({root!r}, 'bmf_chembl_x4.sweep',
                                  seconds=0.3)
print('RESULT', json.dumps(line))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.split("RESULT ", 1)[1])
    assert line["correct"] is False
