"""hlo_cost analyzer: validated against XLA on unrolled graphs (where
XLA's own cost_analysis is correct) and against analytic counts on
scanned graphs (where XLA undercounts — the reason hlo_cost exists)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import (HloCost, analyze, parse_module,
                                   xla_cost_analysis)
from repro.launch.roofline import peaks_for, roofline_terms


def _compile(f, *specs, **jit_kw):
    return jax.jit(f, **jit_kw).lower(*specs).compile()


def test_unrolled_matches_xla():
    def g(x, w):
        for i in range(4):
            x = jnp.tanh(x @ w[i])
        return x
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 256, 256), jnp.float32)
    comp = _compile(g, x, w)
    ours = analyze(comp.as_text())["flops"]
    xla = xla_cost_analysis(comp)["flops"]
    assert ours == pytest.approx(xla, rel=0.01)
    assert ours == pytest.approx(4 * 2 * 256**3, rel=0.01)


def test_scan_trip_count_applied():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((12, 256, 256), jnp.float32)
    comp = _compile(f, x, w)
    ours = analyze(comp.as_text())["flops"]
    assert ours == pytest.approx(12 * 2 * 256**3, rel=0.01)
    # and XLA undercounts — the bug this module works around
    assert xla_cost_analysis(comp)["flops"] < ours / 2


def test_nested_scan():
    def f2(x, w):
        def outer(c, _):
            def body(cc, wi):
                return jnp.tanh(cc @ wi), None
            y, _ = jax.lax.scan(body, c, w)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((5, 128, 128), jnp.float32)
    comp = _compile(f2, x, w)
    ours = analyze(comp.as_text())["flops"]
    assert ours == pytest.approx(15 * 2 * 128**3, rel=0.01)


def test_einsum_batched_dot():
    def f(a, b):
        return jnp.einsum("bik,bkj->bij", a, b)
    a = jax.ShapeDtypeStruct((8, 64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((8, 32, 16), jnp.float32)
    comp = _compile(f, a, b)
    ours = analyze(comp.as_text())["flops"]
    assert ours == pytest.approx(2 * 8 * 64 * 32 * 16, rel=0.01)


def test_parse_tuple_shapes_and_comments():
    text = """
HloModule m

ENTRY %main (p: f32[4,4]) -> (s32[], f32[4,4]) {
  %p = f32[4,4]{1,0} parameter(0)
  %c = s32[] constant(7)
  ROOT %t = (s32[], f32[4,4]{1,0}, /*index=2*/f32[8]{0}) tuple(%c, %p)
}
"""
    comps = parse_module(text)
    assert "ENTRY" in comps
    root = comps["ENTRY"][-1]
    assert root.op == "tuple"
    assert [s.dims for s in root.shapes] == [(), (4, 4), (8,)]
    const = comps["ENTRY"][1]
    assert const.const_val == 7


def test_bytes_accessed_scales_with_trip():
    def f(x, w):
        def body(c, wi):
            return c + wi, None
        y, _ = jax.lax.scan(body, x, w)
        return y
    x = jax.ShapeDtypeStruct((1024,), jnp.float32)
    n1 = analyze(_compile(
        f, x, jax.ShapeDtypeStruct((10, 1024), jnp.float32)).as_text())
    n2 = analyze(_compile(
        f, x, jax.ShapeDtypeStruct((40, 1024), jnp.float32)).as_text())
    assert n2["bytes_accessed"] > 2.5 * n1["bytes_accessed"]


def test_roofline_terms_dominance():
    peaks = peaks_for("TPU v5 lite")
    rec = {"flops": peaks.flops, "bytes_accessed": peaks.hbm_bw / 10,
           "collective_bytes": {"total": peaks.ici_bw / 100},
           "n_chips": 1}

    class Cfg:
        pass
    from repro.configs import get_config, shape_by_name
    cfg = get_config("smollm_135m")
    shape = shape_by_name("train_4k")
    out = roofline_terms(rec, cfg, shape, "TPU v5 lite")
    assert out["dominant"] == "compute"
    assert out["compute_s"] == pytest.approx(1.0)
    assert out["memory_s"] == pytest.approx(0.1)
    assert 0 < out["useful_flop_ratio"]


def test_peaks_unknown_kind_raises():
    """A device kind without published peaks is an error, not a
    default; the v5e entry carries its published figures."""
    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("cpu")


# ---------------------------------------------------------------------------
# collective accounting (async pairs, new kinds, unknown dtypes)
# ---------------------------------------------------------------------------

_SYNC_COLL = """
HloModule sync

ENTRY %main (p0: f32[64,8]) -> (f32[512,8], f32[64,8]) {
  %p0 = f32[64,8]{1,0} parameter(0)
  %ag = f32[512,8]{1,0} all-gather(f32[64,8]{1,0} %p0), dimensions={0}
  %ar = f32[64,8]{1,0} all-reduce(f32[64,8]{1,0} %p0), to_apply=%sum
  ROOT %t = (f32[512,8]{1,0}, f32[64,8]{1,0}) tuple(%ag, %ar)
}
"""

# the same program as XLA emits it with async collectives: a -start
# whose tuple result aliases (operand, result), then a -done
_ASYNC_COLL = """
HloModule async

ENTRY %main (p0: f32[64,8]) -> (f32[512,8], f32[64,8]) {
  %p0 = f32[64,8]{1,0} parameter(0)
  %ags = (f32[64,8]{1,0}, f32[512,8]{1,0}) all-gather-start(f32[64,8]{1,0} %p0), dimensions={0}
  %ag = f32[512,8]{1,0} all-gather-done((f32[64,8]{1,0}, f32[512,8]{1,0}) %ags)
  %ars = f32[64,8]{1,0} all-reduce-start(f32[64,8]{1,0} %p0), to_apply=%sum
  %ar = f32[64,8]{1,0} all-reduce-done(f32[64,8]{1,0} %ars)
  ROOT %t = (f32[512,8]{1,0}, f32[64,8]{1,0}) tuple(%ag, %ar)
}
"""


def test_async_collectives_match_sync_lowering():
    """Regression: an async pair is ONE transfer.  The old analyzer
    charged the -start's aliased tuple at full size and the -done
    again, double-counting every overlapped collective."""
    sync, async_ = analyze(_SYNC_COLL), analyze(_ASYNC_COLL)
    assert sync == async_, (sync, async_)
    # and the numbers are the hand-computed ones, not merely equal
    ag_b, ar_b = 512 * 8 * 4, 64 * 8 * 4
    assert sync["collective_bytes"]["all-gather"] == ag_b
    assert sync["collective_bytes"]["all-reduce"] == 2 * ar_b
    assert sync["collective_bytes"]["total"] == ag_b + 2 * ar_b
    assert sync["bytes_accessed"] == ag_b + ar_b


def test_new_collective_kinds_counted():
    text = """
HloModule kinds

ENTRY %main (p0: f32[64,8]) -> f32[64,8] {
  %p0 = f32[64,8]{1,0} parameter(0)
  %cb = f32[64,8]{1,0} collective-broadcast(f32[64,8]{1,0} %p0)
  %ra = f32[64,8]{1,0} ragged-all-to-all(f32[64,8]{1,0} %cb)
  ROOT %o = f32[64,8]{1,0} add(f32[64,8]{1,0} %cb, f32[64,8]{1,0} %ra)
}
"""
    coll = analyze(text)["collective_bytes"]
    b = 64 * 8 * 4
    assert coll["collective-broadcast"] == b
    assert coll["ragged-all-to-all"] == b
    # and ragged-all-to-all is NOT misfiled under all-to-all
    assert coll["all-to-all"] == 0


def test_unknown_dtype_warns_once_and_counts_zero():
    text = """
HloModule weird

ENTRY %main (p: f4e2m1fnx[32]) -> f4e2m1fnx[32] {
  %p = f4e2m1fnx[32]{0} parameter(0)
  ROOT %n = f4e2m1fnx[32]{0} negate(f4e2m1fnx[32]{0} %p)
}
"""
    import warnings as _w
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        r1 = analyze(text)
        r2 = analyze(text)          # second pass must stay silent
    hits = [str(x.message) for x in rec if "f4e2m1fnx" in str(x.message)]
    assert len(hits) == 1, hits
    assert "unknown HLO dtype" in hits[0]
    assert r1["bytes_accessed"] == 0.0
    assert r1 == r2
