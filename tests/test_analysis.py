"""Self-tests for the static-analysis subsystem (repro.analysis).

The linter is validated two ways: fixture modules under
tests/fixtures/analysis/ carry seeded violations marked with
``# expect: <rule-id>`` comments (every marked line must be found, at
the right line, and nothing else), and the real tree must come back
clean — the linter IS the regression test for the invariants PRs 1–5
earned.

The contract engine is validated arithmetically here (the derivation
for every model-zoo family on both mesh layouts and both pipelines)
and against synthetic HLO with seeded violations; the end-to-end
checks against real lowerings live in tests/test_distributed.py and
the dry-run CLI.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import (CommContract, ContractViolation, Finding,
                            assert_contract, check_compiled,
                            check_lowered, contract_for, lint_paths,
                            resolve_rules)
from repro.analysis.invariants import REPRO_ROOT
from repro.core.blocks import BlockDef, EntityDef, ModelDef
from repro.core.noise import AdaptiveGaussian, FixedGaussian, ProbitNoise
from repro.core.priors import (FixedNormalPrior, MacauPrior, NormalPrior,
                               SpikeAndSlabPrior)

FIXTURES = Path(__file__).parent / "fixtures" / "analysis"
REPO_ROOT = Path(__file__).resolve().parents[1]

BAD_FIXTURES = sorted(p.name for p in FIXTURES.glob("bad_*.py"))


def _expected(path: Path):
    """{(line, rule-id)} read from the fixture's # expect: markers."""
    out = set()
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        if "# expect: " in line:
            out.add((i, line.split("# expect: ", 1)[1].strip()))
    return out


# ---------------------------------------------------------------------------
# linter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", BAD_FIXTURES)
def test_fixture_violations_detected_at_marked_lines(name):
    path = FIXTURES / name
    expected = _expected(path)
    assert expected, f"{name} has no # expect: markers"
    found = {(f.line, f.rule) for f in lint_paths([path])}
    assert found == expected, (name, found, expected)


def test_suppression_comments_silence_findings():
    findings = lint_paths([FIXTURES / "suppressed_clean.py"])
    assert findings == [], [f.format() for f in findings]


def test_clean_tree_zero_findings():
    findings = lint_paths()          # defaults to all of src/repro
    assert findings == [], "\n".join(f.format() for f in findings)
    # and the default target really is the package under test
    assert (REPRO_ROOT / "core" / "gibbs.py").exists()


def test_findings_report_file_line_rule_and_hint():
    f = lint_paths([FIXTURES / "bad_registry_error.py"])[0]
    assert isinstance(f, Finding)
    txt = f.format()
    assert f"bad_registry_error.py:{f.line}:" in txt
    assert "[registry-error-without-choices]" in txt
    assert "fix:" in txt


def test_resolve_rules_names_choices_on_typo():
    assert [r.id for r in resolve_rules("nondeterminism-in-core")] == \
        ["nondeterminism-in-core"]
    with pytest.raises(ValueError, match="valid rules.*batch-rng"):
        resolve_rules("no-such-rule")


def test_rule_selection_scopes_the_pass():
    path = FIXTURES / "bad_sweep_rng.py"
    only_imports = lint_paths(
        [path], resolve_rules("experimental-import-outside-compat"))
    assert only_imports == []


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _run_cli(*args, timeout=240):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO_ROOT / "src")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=REPO_ROOT)


@pytest.mark.slow
def test_cli_exits_zero_on_repo_tree():
    out = _run_cli()
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stderr


@pytest.mark.slow
@pytest.mark.parametrize("name", BAD_FIXTURES)
def test_cli_exits_nonzero_on_each_seeded_fixture(name):
    out = _run_cli(str(FIXTURES / name))
    assert out.returncode == 1, out.stdout + out.stderr
    for line, rule_id in _expected(FIXTURES / name):
        assert f"{name}:{line}: [{rule_id}]" in out.stdout, \
            (name, line, rule_id, out.stdout)


# ---------------------------------------------------------------------------
# contract derivation (pure arithmetic; real-lowering checks live in
# test_distributed.py and the dry-run CLI)
# ---------------------------------------------------------------------------

K = 8


def _two_entity(noise=None, row_prior=None, bf16=False):
    return ModelDef(
        (EntityDef("r", 96, row_prior or NormalPrior(K)),
         EntityDef("c", 48, NormalPrior(K))),
        (BlockDef(0, 1, noise or FixedGaussian(5.0), sparse=True),),
        K, use_pallas=False, bf16_gather=bf16)


def _gfa_model():
    ents = [EntityDef("z", 96, FixedNormalPrior(K)),
            EntityDef("v0", 48, SpikeAndSlabPrior(K)),
            EntityDef("v1", 24, SpikeAndSlabPrior(K))]
    blocks = (BlockDef(0, 1, AdaptiveGaussian(), sparse=False),
              BlockDef(0, 2, AdaptiveGaussian(), sparse=False))
    return ModelDef(tuple(ents), blocks, K)


ZOO = {
    "gaussian": (_two_entity(), 2, 6, K * K, "f32"),
    "probit": (_two_entity(noise=ProbitNoise()), 2, 6, K * K, "f32"),
    "bf16": (_two_entity(bf16=True), 2, 6, K * K, "bf16"),
    "macau": (_two_entity(row_prior=MacauPrior(K, 12)), 2, 7,
              12 * K, "f32"),
    "gfa": (_gfa_model(), 3, 8, K, "f32"),
}


@pytest.mark.parametrize("mesh_shape", [(8,), (4, 2)])
@pytest.mark.parametrize("name", sorted(ZOO))
@pytest.mark.parametrize("pipeline", ["eager", "ring"])
def test_contract_for_model_zoo(name, mesh_shape, pipeline):
    model, E, ar, max_elems, wire = ZOO[name]
    c = contract_for(model, mesh_shape, pipeline)
    S = 8
    assert c.n_shards == S
    assert c.all_reduces == ar
    assert c.max_reduce_elems == max_elems
    assert c.wire_dtype == wire
    if pipeline == "ring":
        # zero full-factor gathers in ring mode — the limited-
        # communication guarantee — and E circulations of S-1 hops
        assert c.all_gathers == 0
        assert c.collective_permutes == E * (S - 1)
    else:
        assert c.all_gathers == E
        assert c.collective_permutes == 0


def test_contract_for_validates_pipeline_choices():
    with pytest.raises(ValueError, match="valid pipelines"):
        contract_for(_two_entity(), (8,), "warp")


def test_contract_for_rejects_unknown_prior():
    class MysteryPrior:
        num_latent = K

    model = ModelDef((EntityDef("r", 96, MysteryPrior()),),
                     (), K)
    with pytest.raises(ValueError, match="NormalPrior"):
        contract_for(model, (8,), "eager")


# ---------------------------------------------------------------------------
# contract checking against synthetic IR with seeded violations
# ---------------------------------------------------------------------------

_FAKE_STABLEHLO = """
module @jit_step {
  func.func public @main(%arg0: tensor<12x8xf32>) {
    %0 = "stablehlo.all_gather"(%arg0) : (tensor<12x8xf32>) -> tensor<96x8xf32>
    %1 = "stablehlo.all_gather"(%arg0) : (tensor<12x8xf32>) -> tensor<96x8xf32>
    %2 = "stablehlo.all_reduce"(%arg0) : (tensor<8x8xf32>) -> tensor<8x8xf32>
    %3 = "stablehlo.all_reduce"(%arg0) : (tensor<8x8xf32>) -> tensor<8x8xf32>
    %4 = "stablehlo.all_reduce"(%arg0) : (tensor<f32>) -> tensor<f32>
    %5 = "stablehlo.all_reduce"(%arg0) : (tensor<f32>) -> tensor<f32>
    %6 = "stablehlo.all_reduce"(%arg0) : (tensor<f32>) -> tensor<f32>
    %7 = "stablehlo.all_reduce"(%arg0) : (tensor<f32>) -> tensor<f32>
  }
}
"""

_FAKE_HLO = """
HloModule jit_step

ENTRY %main (p0: f32[12,8]) -> f32[96,8] {
  %p0 = f32[12,8]{1,0} parameter(0)
  %ag0 = f32[96,8]{1,0} all-gather(f32[12,8]{1,0} %p0), dimensions={0}
  %ag1 = f32[96,8]{1,0} all-gather(f32[12,8]{1,0} %p0), dimensions={0}
  %ar0 = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} %p0), to_apply=%add
  ROOT %out = f32[96,8]{1,0} add(f32[96,8]{1,0} %ag0, f32[96,8]{1,0} %ag1)
}
"""


def _eager_contract(ar=6):
    return CommContract(pipeline="eager", n_shards=8, all_gathers=2,
                        collective_permutes=0, all_reduces=ar,
                        max_reduce_elems=K * K, wire_dtype="f32")


def test_check_lowered_passes_matching_module():
    assert check_lowered(_eager_contract(), _FAKE_STABLEHLO) == []


def test_check_lowered_catches_count_and_dtype_violations():
    # one gather too many expected -> count violation
    bad = _eager_contract()
    bad = CommContract(**{**bad.asdict(), "all_gathers": 3})
    msgs = check_lowered(bad, _FAKE_STABLEHLO)
    assert any("all-gather" in m for m in msgs), msgs
    # bf16 contract against an f32 wire -> dtype violation
    bad = CommContract(**{**_eager_contract().asdict(),
                          "wire_dtype": "bf16"})
    msgs = check_lowered(bad, _FAKE_STABLEHLO)
    assert any("wire" in m for m in msgs), msgs


def test_check_compiled_counts_and_payload_bound():
    assert check_compiled(_eager_contract(), _FAKE_HLO) == []
    # a ring contract must reject the gathers outright
    ring = CommContract(pipeline="ring", n_shards=8, all_gathers=0,
                        collective_permutes=14, all_reduces=6,
                        max_reduce_elems=K * K, wire_dtype="f32")
    msgs = check_compiled(ring, _FAKE_HLO)
    assert any("all-gather" in m for m in msgs), msgs
    assert any("collective-permute" in m for m in msgs), msgs
    # payload bound: an all-reduce bigger than max_reduce_elems fails
    tight = CommContract(**{**_eager_contract().asdict(),
                            "max_reduce_elems": 4})
    msgs = check_compiled(tight, _FAKE_HLO)
    assert any("payload" in m for m in msgs), msgs


def test_assert_contract_raises_with_every_violation():
    ring = CommContract(pipeline="ring", n_shards=8, all_gathers=0,
                        collective_permutes=14, all_reduces=6,
                        max_reduce_elems=K * K, wire_dtype="f32")
    with pytest.raises(ContractViolation, match="all-gather"):
        assert_contract(ring, lowered_text=_FAKE_STABLEHLO,
                        compiled_text=_FAKE_HLO, where="synthetic")
    # the passing direction raises nothing
    assert_contract(_eager_contract(), lowered_text=_FAKE_STABLEHLO,
                    compiled_text=_FAKE_HLO)


# ---------------------------------------------------------------------------
# dry-run JSON audit
# ---------------------------------------------------------------------------

DRYRUN = REPO_ROOT / "results" / "dryrun"


@pytest.mark.slow
def test_committed_dryrun_jsons_carry_valid_contracts():
    """Every committed dry-run record stores the contract its HLO was
    verified against, and re-deriving it from the cell reproduces it
    (audited in-process; CI also runs the CLI equivalent)."""
    from repro.analysis.contract import dryrun_contract_findings
    jsons = sorted(DRYRUN.glob("*.json"))
    assert jsons, "no committed dry-run JSONs"
    for j in jsons:
        assert dryrun_contract_findings(j) == [], j.name
        rec = json.loads(j.read_text())
        assert rec["contract_ok"] is True, j.name


@pytest.mark.slow
def test_cli_contract_audit_catches_tampered_json(tmp_path):
    """--contracts on a doctored record (ring claiming all-gathers)
    exits nonzero naming the mismatched field."""
    src = sorted(DRYRUN.glob("*.ring.json"))
    assert src, "no committed ring dry-run JSON"
    rec = json.loads(src[0].read_text())
    rec["contract"]["all_gathers"] = 2          # rings gather nothing
    (tmp_path / src[0].name).write_text(json.dumps(rec))
    out = _run_cli("--contracts", str(tmp_path))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "all_gathers" in out.stdout


# ---------------------------------------------------------------------------
# observability schema audit (PR 10)
# ---------------------------------------------------------------------------

OBS_SAMPLES = REPO_ROOT / "results" / "obs"


def test_committed_obs_samples_pass_schema_audit():
    """Every committed results/obs sample is a well-formed repro.obs
    export (audited in-process; CI also runs the CLI equivalent)."""
    from repro.analysis.obsschema import obs_schema_findings
    jsons = sorted(OBS_SAMPLES.glob("*.json"))
    assert len(jsons) >= 3, "expected trace + metrics + serve samples"
    for j in jsons:
        assert obs_schema_findings(j) == [], j.name


@pytest.mark.slow
def test_cli_obs_audit_catches_tampered_samples(tmp_path):
    """--obs on doctored samples (sweep span missing bytes_on_wire;
    histogram total drifted off sum(counts); serve snapshot missing a
    required histogram) exits nonzero naming each defect."""
    trace = json.loads((OBS_SAMPLES / "train_trace.json").read_text())
    for ev in trace["traceEvents"]:
        if ev["name"] == "sweep":
            ev["args"].pop("bytes_on_wire", None)
    (tmp_path / "train_trace.json").write_text(json.dumps(trace))

    met = json.loads((OBS_SAMPLES / "serve_metrics.json").read_text())
    met["histograms"]["serve.execute_s"]["total"] += 1
    del met["histograms"]["serve.queue_wait_s"]
    (tmp_path / "serve_metrics.json").write_text(json.dumps(met))

    out = _run_cli("--obs", str(tmp_path))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "bytes_on_wire" in out.stdout
    assert "sum(counts)" in out.stdout
    assert "serve.queue_wait_s" in out.stdout


# ---------------------------------------------------------------------------
# Pallas kernel contract verifier (PR 8)
# ---------------------------------------------------------------------------

KERNEL_FIXTURES = sorted(p.name for p in FIXTURES.glob("kernel_bad_*.py"))


def test_kernel_fixture_set_is_complete():
    """One seeded fixture per kernel rule (the four contract classes)."""
    assert KERNEL_FIXTURES == [
        "kernel_bad_bounds.py", "kernel_bad_dtype.py",
        "kernel_bad_race.py", "kernel_bad_vmem.py"]


def test_kernel_rules_registered_in_catalogue():
    from repro.analysis import KERNEL_RULE_IDS
    from repro.analysis.invariants import RULES
    for rid in KERNEL_RULE_IDS:
        assert rid in RULES
        assert [r.id for r in resolve_rules(rid)] == [rid]


@pytest.mark.parametrize("name", KERNEL_FIXTURES)
def test_kernel_fixture_violations_at_marked_lines(name):
    from repro.analysis import check_kernel_paths
    path = FIXTURES / name
    expected = _expected(path)
    assert expected, f"{name} has no # expect: markers"
    found = {(f.line, f.rule)
             for f in check_kernel_paths([path])}
    assert found == expected, (name, found, expected)


def test_kernel_rule_selection_scopes_the_pass():
    from repro.analysis import check_kernel_paths
    only_vmem = check_kernel_paths(
        [FIXTURES / "kernel_bad_race.py"],
        resolve_rules("kernel-vmem-budget"))
    assert only_vmem == []


def test_kernel_suppression_comment_silences_finding(tmp_path):
    from repro.analysis import check_kernel_paths
    src = (FIXTURES / "kernel_bad_race.py").read_text()
    quiet = tmp_path / "kernel_suppressed.py"
    quiet.write_text(src.replace(
        "# expect: kernel-output-race",
        "# repro-lint: disable=kernel-output-race"))
    assert check_kernel_paths([quiet]) == []


def test_kernel_file_without_registry_is_an_error(tmp_path):
    from repro.analysis import check_kernel_paths
    bare = tmp_path / "no_registry.py"
    bare.write_text("x = 1\n")
    with pytest.raises(ValueError, match="KERNELS registry"):
        check_kernel_paths([bare])


@pytest.mark.slow
def test_shipped_kernel_registry_proves_clean():
    """The real tree: all five kernels, all shipped block configs —
    race-free, in bounds, fp32-accumulating, inside VMEM budget."""
    from repro.analysis import check_kernels, vmem_report
    findings = check_kernels()
    assert findings == [], "\n".join(f.format() for f in findings)
    report = vmem_report()
    assert sorted(report) == ["chol_draw", "flash", "gram", "sddmm",
                              "topk_score"]
    for name, r in report.items():
        assert r["ok"], (name, r)
        assert 0 < r["peak_bytes"] <= r["budget_bytes"], (name, r)


@pytest.mark.slow
def test_kernel_capture_is_repeatable():
    """Back-to-back captures see every pallas_call site both times
    (jit/eval_shape caches must not swallow the second pass) and the
    kernels still execute correctly afterwards (the capture shim must
    not poison real traces)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.kernelcheck import capture_spec
    from repro.kernels import ops, ref
    spec = ops.KERNELS["gram"]
    first = capture_spec(spec)
    second = capture_spec(spec)
    assert len(first) == len(second) == len(spec.probes)
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    vg = jax.random.normal(k1, (8, 128, 16), jnp.float32)
    val = jax.random.normal(k2, (8, 128), jnp.float32)
    mask = (jax.random.uniform(k3, (8, 128)) > 0.3).astype(jnp.float32)
    g1, r1 = ops.gram_and_rhs(vg, val, mask, use_pallas=True)
    g2, r2 = ref.gram_ref(vg, val, mask)
    np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-4)
    assert float(jnp.sum(jnp.abs(g1))) > 0   # not the shim's zeros


@pytest.mark.slow
def test_cli_kernels_exits_zero_on_shipped_registry():
    out = _run_cli("--kernels", timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stderr


@pytest.mark.slow
@pytest.mark.parametrize("name", KERNEL_FIXTURES)
def test_cli_kernels_exits_nonzero_on_each_seeded_fixture(name):
    out = _run_cli("--kernels", str(FIXTURES / name), timeout=600)
    assert out.returncode == 1, out.stdout + out.stderr
    for line, rule_id in _expected(FIXTURES / name):
        assert f"{name}:{line}: [{rule_id}]" in out.stdout, \
            (name, line, rule_id, out.stdout)


# ---------------------------------------------------------------------------
# --json output mode (CI turns these into GitHub annotations)
# ---------------------------------------------------------------------------

def test_json_mode_emits_machine_readable_findings(capsys):
    from repro.analysis.__main__ import main
    rc = main([str(FIXTURES / "bad_registry_error.py"), "--json"])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["count"] == len(payload["findings"]) > 0
    f = payload["findings"][0]
    assert set(f) == {"path", "line", "rule", "message", "hint"}
    assert f["rule"] == "registry-error-without-choices"
    assert f["line"] > 0 and f["hint"]


def test_json_mode_clean_input_is_empty_payload(capsys):
    from repro.analysis.__main__ import main
    rc = main([str(FIXTURES / "suppressed_clean.py"), "--json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload == {"findings": [], "count": 0}


def test_github_annotations_script_formats_findings(tmp_path):
    script = REPO_ROOT / "scripts_dev" / "github_annotations.py"
    payload = json.dumps({"findings": [
        {"path": "src/x.py", "line": 7, "rule": "some-rule",
         "message": "broke it", "hint": "fix it"}], "count": 1})
    out = subprocess.run(
        [sys.executable, str(script)], input=payload,
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 1
    assert "::error file=src/x.py,line=7,title=some-rule::" in out.stdout
    assert "fix it" in out.stdout
    clean = subprocess.run(
        [sys.executable, str(script)],
        input='{"findings": [], "count": 0}',
        capture_output=True, text=True, timeout=60)
    assert clean.returncode == 0


# ---------------------------------------------------------------------------
# kernel_vmem column in the dry-run audit
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_dryrun_kernel_vmem_audit_catches_tampering(tmp_path):
    """The committed records' kernel_vmem estimates must match a fresh
    capture; a doctored peak or a dropped column is a finding."""
    from repro.analysis.contract import dryrun_contract_findings
    src = sorted(DRYRUN.glob("*.json"))[0]
    rec = json.loads(src.read_text())
    assert rec["kernel_vmem_ok"] is True
    rec["kernel_vmem"]["gram"]["peak_bytes"] = 1
    doctored = tmp_path / src.name
    doctored.write_text(json.dumps(rec))
    msgs = dryrun_contract_findings(doctored)
    assert any("kernel_vmem" in m and "peak_bytes" in m for m in msgs), \
        msgs

    rec = json.loads(src.read_text())
    del rec["kernel_vmem"]
    doctored.write_text(json.dumps(rec))
    msgs = dryrun_contract_findings(doctored)
    assert any("missing kernel_vmem" in m for m in msgs), msgs
