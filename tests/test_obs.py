"""The ``repro.obs`` observability subsystem.

Four contract families:

1. **Primitives** — fixed-bucket histograms (observe/percentile/
   serialization round-trip), recorder span/counter/gauge semantics,
   trace + metrics + Prometheus export formats.
2. **Determinism** — a DISABLED recorder never reads the clock and
   records nothing (the on/off bitwise-equality side lives in
   tests/test_golden_chain.py and tests/test_multichain.py).
3. **Wiring** — ``REPRO_OBS=1`` makes an ordinary ``TrainSession``
   emit a loadable Chrome trace with contract-derived
   ``bytes_on_wire`` on every sweep span; ``PredictSession`` exposes
   cache hit/miss stats; the module-level spec cache is a bounded LRU.
4. **One clock** — every span reaches the JAX profiler's trace, on the
   thread that did the work and inside its parent, with the recorder
   on or off; ``gc`` spans count collections; the event ring is
   bounded; the sweep's stages are named in the compiled HLO.
"""
import json
import math
import os

import numpy as np
import pytest

from repro.obs import (Histogram, METRICS_FORMAT, TRACE_FORMAT,
                       Recorder, integer_buckets, latency_buckets,
                       obs_enabled, percentile_summary,
                       prometheus_text, resolve_recorder,
                       write_json_atomic)


# ---------------------------------------------------------------------------
# histogram primitives
# ---------------------------------------------------------------------------

def test_latency_buckets_geometric_and_bounded():
    b = latency_buckets()
    assert b[0] == pytest.approx(1e-4)
    assert all(y > x for x, y in zip(b, b[1:]))
    assert b[-1] >= 120.0
    # geometric: constant ratio
    ratios = [y / x for x, y in zip(b, b[1:])]
    assert max(ratios) - min(ratios) < 1e-9


def test_integer_buckets_count_exactly():
    h = Histogram(integer_buckets(4))
    for occ, times in ((1, 3), (2, 1), (4, 2)):
        for _ in range(times):
            h.observe(occ)
    # exact counts: 0.5/1.5/2.5/3.5/4.5 edges isolate each integer
    assert h.counts[1] == 3 and h.counts[2] == 1 and h.counts[4] == 2
    assert h.total == 6
    assert h.mean() == pytest.approx((1 * 3 + 2 + 4 * 2) / 6, rel=0.5)


def test_histogram_percentile_interpolates():
    h = Histogram([1.0, 2.0, 4.0, 8.0])
    for v in (0.5, 1.5, 1.6, 3.0, 6.0):
        h.observe(v)
    assert 0.0 <= h.percentile(0.0) <= 1.0
    assert 1.0 <= h.percentile(0.5) <= 4.0
    assert h.percentile(0.5) == pytest.approx(1.75)  # interpolated
    assert h.percentile(1.0) <= 8.0
    with pytest.raises(ValueError):
        h.percentile(1.5)


def test_histogram_overflow_and_empty():
    h = Histogram([1.0, 2.0])
    assert math.isnan(h.percentile(0.5))    # empty
    h.observe(100.0)                        # overflow bucket
    assert h.counts[-1] == 1
    assert h.percentile(0.99) == 2.0        # clamped to last bound
    assert h.sum == pytest.approx(100.0)


def test_histogram_dict_round_trip_and_validation():
    h = Histogram(latency_buckets())
    for v in (0.001, 0.01, 0.01, 5.0):
        h.observe(v)
    d = h.to_dict()
    assert len(d["counts"]) == len(d["bounds"]) + 1
    assert d["total"] == 4
    h2 = Histogram.from_dict(d)
    assert h2.counts == h.counts and h2.bounds == h.bounds
    assert h2.percentile(0.5) == h.percentile(0.5)
    bad = dict(d, counts=d["counts"][:-1])
    with pytest.raises(ValueError):
        Histogram.from_dict(bad)


def test_percentile_summary_keys():
    h = Histogram(latency_buckets())
    h.observe(0.02)
    s = percentile_summary(h)
    assert set(s) == {"p50", "p99", "mean", "count"}
    assert s["count"] == 1


# ---------------------------------------------------------------------------
# recorder semantics
# ---------------------------------------------------------------------------

def test_disabled_recorder_records_nothing_and_skips_clock():
    rec = Recorder(enabled=False)
    assert rec.now() == 0.0     # no clock read on the off path
    with rec.span("x", cat="t"):
        pass
    rec.add("c")
    rec.gauge("g", 1.0)
    rec.observe("h", 0.5)
    assert rec.trace()["traceEvents"] == []
    m = rec.metrics()
    assert m["counters"] == {} and m["gauges"] == {} \
        and m["histograms"] == {}


def test_recorder_span_counter_gauge_and_trace_shape():
    rec = Recorder(enabled=True)
    rec.set_kind("session")
    with rec.span("phase/work", cat="test", step=3):
        pass
    rec.add("n", 2)
    rec.add("n")
    rec.gauge("depth", 4.0)
    rec.observe("lat", 0.01)

    tr = rec.trace()
    assert tr["repro"] == {"format": TRACE_FORMAT, "kind": "session"}
    by_name = {e["name"]: e for e in tr["traceEvents"]}
    span = by_name["phase/work"]
    assert span["ph"] == "X" and span["dur"] >= 0 \
        and span["args"]["step"] == 3

    m = rec.metrics()
    assert m["format"] == METRICS_FORMAT and m["kind"] == "session"
    assert m["counters"]["n"] == 3.0
    assert m["gauges"]["depth"] == 4.0
    assert m["histograms"]["lat"]["total"] == 1

    rec.reset()
    assert rec.trace()["traceEvents"] == []
    assert rec.metrics()["counters"] == {}


def test_prometheus_text_exposition():
    rec = Recorder(enabled=True)
    rec.add("serve.completed", 5)
    rec.gauge("ckpt.queue_depth", 1.0)
    rec.observe("lat", 0.5, bounds=[1.0, 2.0])
    text = rec.prometheus()
    assert "repro_serve_completed 5" in text
    assert "repro_ckpt_queue_depth 1" in text
    assert 'repro_lat_bucket{le="1' in text
    assert 'le="+Inf"' in text
    assert "repro_lat_count 1" in text
    # standalone renderer agrees (TYPE header then the sample line)
    assert "\nrepro_a_b 1" in prometheus_text({"a.b": 1.0}, {}, {})


def test_obs_enabled_and_resolve_recorder(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    assert not obs_enabled()
    assert not resolve_recorder(None).enabled
    monkeypatch.setenv("REPRO_OBS", "1")
    assert obs_enabled()
    assert resolve_recorder(None).enabled
    # fresh per call — two runs never interleave traces
    assert resolve_recorder(None) is not resolve_recorder(None)
    mine = Recorder(enabled=False)
    assert resolve_recorder(mine) is mine


def test_write_json_atomic(tmp_path):
    p = tmp_path / "sub" / "x.json"
    write_json_atomic(p, {"a": 1})
    assert json.loads(p.read_text()) == {"a": 1}
    assert [f.name for f in (tmp_path / "sub").iterdir()] == ["x.json"]


# ---------------------------------------------------------------------------
# session wiring: REPRO_OBS=1 emits a loadable trace
# ---------------------------------------------------------------------------

def _toy_train(tmp_path, **kw):
    from repro.core import TrainSession
    from repro.core.sparse import random_sparse
    mat, _, _ = random_sparse(3, (40, 24), 0.3, rank=3)
    s = TrainSession(num_latent=4, burnin=2, nsamples=2, seed=3,
                     chains=1, save_freq=1,
                     save_dir=str(tmp_path / "store"), **kw)
    s.add_train_and_test(mat)
    return s.run()


def test_repro_obs_env_emits_loadable_trace(tmp_path, monkeypatch):
    """The acceptance path: REPRO_OBS=1 + REPRO_OBS_DIR, an ordinary
    TrainSession run, and the exported Chrome trace carries sweep
    spans with contract bytes_on_wire plus the compile split — and
    both exports pass the CI schema audit."""
    from repro.analysis.obsschema import obs_schema_findings

    out = tmp_path / "obs_out"
    monkeypatch.setenv("REPRO_OBS", "1")
    monkeypatch.setenv("REPRO_OBS_DIR", str(out))
    r = _toy_train(tmp_path)

    trace_p = out / "train_trace.json"
    metrics_p = out / "train_metrics.json"
    assert trace_p.is_file() and metrics_p.is_file()
    assert obs_schema_findings(trace_p) == []
    assert obs_schema_findings(metrics_p) == []

    doc = json.loads(trace_p.read_text())
    assert doc["repro"]["kind"] == "session"
    sweeps = [e for e in doc["traceEvents"] if e["name"] == "sweep"]
    assert len(sweeps) == 4     # burnin 2 + nsamples 2
    assert {e["args"]["phase"] for e in sweeps} == {"burnin", "sample"}
    assert all(isinstance(e["args"]["bytes_on_wire"], int)
               for e in sweeps)
    assert sweeps[0]["args"]["stage"] == "first"
    assert [e["args"]["sweep"] for e in sweeps] == [0, 1, 2, 3]
    compiles = [e for e in doc["traceEvents"]
                if e["name"] == "session/compile"]
    assert len(compiles) == 1

    met = json.loads(metrics_p.read_text())
    assert met["counters"]["session.sweeps"] == 4.0
    assert met["counters"]["ckpt.saves"] >= 1.0
    assert "session.sweep_s" in met["histograms"]

    # satellite 1: the runtime split is additive and JSON-visible
    d = r.to_dict()
    assert d["compile_s"] > 0.0
    assert d["total_s"] == pytest.approx(d["compile_s"]
                                         + d["runtime_s"])
    json.dumps(d)   # serializable end to end


def test_obs_off_session_exports_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    monkeypatch.delenv("REPRO_OBS_DIR", raising=False)
    r = _toy_train(tmp_path)
    assert not (tmp_path / "store" / "obs").exists()
    # the compile/runtime split is measured regardless of obs
    assert r.compile_s > 0.0 and r.runtime_s > 0.0


# ---------------------------------------------------------------------------
# predict/serve wiring: cache stats + bounded spec cache
# ---------------------------------------------------------------------------

def test_predict_cache_stats_and_serve_snapshot(tmp_path):
    from repro.core import PredictSession
    from repro.launch.serve import RecommendServer

    _toy_train(tmp_path)
    store = str(tmp_path / "store")
    ps = PredictSession(store)
    ps.warm_cache()     # miss
    ps.warm_cache()     # hit
    st = ps.cache_stats()
    assert st["misses"] == 1 and st["hits"] == 1
    assert st["over_budget"] == 0
    assert st["resident"] is True
    assert st["resident_bytes"] > 0
    assert st["load_count"] >= 1
    assert st["spec_cache"]["size"] <= st["spec_cache"]["max_size"]

    # a store bigger than the budget refuses residency and counts it
    tiny = PredictSession(store, cache_bytes=16)
    assert tiny.warm_cache() is None
    t = tiny.cache_stats()
    assert t["over_budget"] == 1 and t["resident"] is False

    srv = RecommendServer(ps, slots=2, k=3)
    for u in range(4):
        srv.submit(user=u)
    srv.run()
    snap = srv.metrics_snapshot()
    assert snap["kind"] == "serve"
    assert snap["counters"]["serve.completed"] == 4.0
    for name in ("serve.queue_wait_s", "serve.execute_s",
                 "serve.batch_occupancy"):
        assert name in snap["histograms"], name
    occ = Histogram.from_dict(snap["histograms"]
                              ["serve.batch_occupancy"])
    assert 1.0 <= occ.mean() <= 2.0     # slots=2 bound respected


def test_spec_cache_is_a_bounded_lru(tmp_path, monkeypatch):
    from repro.core import predict

    monkeypatch.setattr(predict, "_SPEC_CACHE_MAX", 2)
    predict._SPEC_CACHE.clear()
    for k in ("hits", "misses", "evictions"):
        predict._SPEC_CACHE_STATS[k] = 0

    stores = []
    for i in range(3):
        d = tmp_path / f"s{i}"
        _toy_train(tmp_path / f"t{i}")
        os.rename(tmp_path / f"t{i}" / "store", d)
        stores.append(str(d))

    for s in stores:
        predict.PredictSession(s)
    assert len(predict._SPEC_CACHE) == 2        # bounded
    st = predict.spec_cache_stats()
    assert st["misses"] == 3 and st["evictions"] == 1
    # LRU: oldest store evicted, newest two resident
    predict.PredictSession(stores[2])
    assert predict.spec_cache_stats()["hits"] >= 1


# ---------------------------------------------------------------------------
# one clock: the program's spans in the JAX profiler's trace
# ---------------------------------------------------------------------------

def _profiled(tmp_path, fn):
    """Run ``fn`` under a JAX profiler trace; return the spans of each
    host Python thread, one ``[(start, end, name)]`` list per thread."""
    import jax
    from jax.profiler import ProfileData

    d = tmp_path / "profile"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = d.rglob("*.xplane.pb")
    return [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for e in ln.events]
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:CPU")
            for ln in plane.lines if ln.name.startswith("python")]


def _inside(thread, child, parent):
    """Every ``child`` span of the thread lies within a ``parent``."""
    kids = [s for s in thread if s[2] == child]
    outer = [s for s in thread if s[2] == parent]
    return bool(kids) and all(
        any(p[0] <= k[0] and k[1] <= p[1] for p in outer) for k in kids)


def test_program_spans_land_in_the_profiler_trace(tmp_path, monkeypatch):
    """With ``REPRO_OBS`` unset, a traced run puts every span of the
    sweep loop, the sample writer and the serving step into the
    profiler's trace, each on the thread that did the work and nested
    inside its parent."""
    import gc

    from repro.core import PredictSession
    from repro.launch.serve import RecommendServer

    monkeypatch.delenv("REPRO_OBS", raising=False)
    gc_in = {}

    def work():
        _toy_train(tmp_path, callbacks=[lambda info: gc.collect()])
        ps = PredictSession(str(tmp_path / "store"))
        srv = RecommendServer(ps, slots=2, k=3)
        user_rows = ps.user_rows

        def rows(*a, **kw):
            gc.collect()
            return user_rows(*a, **kw)

        monkeypatch.setattr(ps, "user_rows", rows)
        for u in range(3):
            srv.submit(user=u, exclude=[0])
        srv.run()
        gc_in["served"] = len(srv.done)

    threads = _profiled(tmp_path, work)
    assert gc_in["served"] == 3
    (loop,) = [t for t in threads if any(s[2] == "sweep" for s in t)]
    for child, parent in (("session/readback", "sweep"),
                          ("ckpt/wait", "session/save"),
                          ("ckpt/host_copy", "session/save"),
                          ("predict/rows", "serve/step"),
                          ("predict/mask", "serve/step"),
                          ("predict/score", "serve/step"),
                          ("predict/readback", "serve/step"),
                          ("serve/finish", "serve/step")):
        assert _inside(loop, child, parent), (child, parent)
    names = {s[2] for s in loop}
    assert {"session/accumulate", "session/callbacks",
            "serve/admit"} <= names
    # the collections forced in a callback and in the rows' gather
    gcs = [s for s in loop if s[2] == "gc"]
    for parent in ("session/callbacks", "predict/rows"):
        assert any(p[0] <= g[0] and g[1] <= p[1] for g in gcs
                   for p in loop if p[2] == parent), parent
    # the sample writer's span is on the writer's own thread
    assert "ckpt/save" not in names
    assert any(s[2] == "ckpt/save" for t in threads if t is not loop
               for s in t)


def test_disabled_recorder_annotates_but_records_nothing(tmp_path,
                                                         monkeypatch):
    """Off, a span still reaches the profiler's trace; the recorder
    reads no clock and keeps no event."""
    import gc

    from repro.obs import clock

    def no_clock():
        raise AssertionError("a disabled recorder read the clock")

    monkeypatch.setattr(clock, "perf_counter", no_clock)
    monkeypatch.setattr(clock, "monotonic", no_clock)
    rec = Recorder(enabled=False)

    def work():
        with rec.gc_spans():
            with rec.span("layer/phase", step=1):
                gc.collect()

    threads = _profiled(tmp_path, work)
    (main,) = [t for t in threads if any(s[2] == "layer/phase" for s in t)]
    assert _inside(main, "gc", "layer/phase")
    assert rec.trace()["traceEvents"] == []
    assert rec.metrics()["counters"] == {}


def test_gc_spans_record_each_collection(tmp_path, monkeypatch):
    """An enabled recorder keeps one ``gc`` event per collection during
    ``Session.run`` and ``RecommendServer.run``, with its generation
    and the objects collected."""
    import gc

    from repro.core import PredictSession
    from repro.launch.serve import RecommendServer

    was = gc.isenabled()
    gc.disable()          # only the collections forced below
    try:
        rec = Recorder(enabled=True)
        _toy_train(tmp_path, recorder=rec,
                   callbacks=[lambda info: gc.collect(1)])
        gcs = [e for e in rec.trace()["traceEvents"] if e["name"] == "gc"]
        assert len(gcs) == 4                    # one per sweep
        assert {e["args"]["generation"] for e in gcs} == {1}
        assert all(e["args"]["collected"] >= 0 for e in gcs)

        srv_rec = Recorder(enabled=True)
        ps = PredictSession(str(tmp_path / "store"))
        srv = RecommendServer(ps, slots=2, k=3, recorder=srv_rec)
        gc.collect()                            # outside run: not kept
        for u in range(4):
            srv.submit(user=u)
        monkeypatch.setattr(srv, "_finish", lambda s, f=srv._finish:
                            (gc.collect(), f(s)))
        srv.run()
        assert sum(e["name"] == "gc" for e in
                   srv_rec.trace()["traceEvents"]) == 4
    finally:
        if was:
            gc.enable()
    # the hooks are removed on exit
    assert not any(getattr(cb, "__qualname__", "").startswith(
        "Recorder.gc_spans") for cb in gc.callbacks)


def test_gc_inside_a_locked_section_is_recorded():
    """A collection can interrupt the recorder's own locked sections (a
    sample writer's span, a histogram update); its span is recorded
    there instead of deadlocking."""
    import gc
    import threading

    rec = Recorder(enabled=True)

    def work():
        with rec.gc_spans():
            with rec._lock:
                gc.collect()
            rec.trace()

    t = threading.Thread(target=work, daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert [e["name"] for e in rec.trace()["traceEvents"]] == ["gc"]


def test_serve_step_span_carries_step_and_ids(tmp_path):
    from repro.core import PredictSession
    from repro.launch.serve import RecommendServer

    _toy_train(tmp_path)
    srv = RecommendServer(PredictSession(str(tmp_path / "store")),
                          slots=2, k=3)
    ids = [srv.submit(user=u) for u in range(3)]
    srv.run()
    steps = [e for e in srv.obs.trace()["traceEvents"]
             if e["name"] == "serve/step"]
    assert [e["args"]["step"] for e in steps] == [0, 1]
    assert [e["args"]["ids"] for e in steps] == [ids[:2], ids[2:]]
    assert [e["args"]["batch"] for e in steps] == [2, 1]


def test_event_ring_drops_the_oldest_and_counts_them():
    from repro.obs.recorder import MAX_EVENTS

    assert MAX_EVENTS == 65_536
    rec = Recorder(enabled=True)
    for i in range(MAX_EVENTS + 10):
        rec.complete("e", 0.0, end=0.0, i=i)
    events = rec.trace()["traceEvents"]
    assert len(events) == MAX_EVENTS
    assert events[0]["args"]["i"] == 10
    assert events[-1]["args"]["i"] == MAX_EVENTS + 9
    assert rec.counter("obs.events_dropped") == 10.0


@pytest.mark.parametrize("program", ["gibbs_step", "sharded_sweep"])
def test_sweep_stages_are_named_in_the_compiled_module(program):
    """The sweep's stages carry ``jax.named_scope`` names into the
    compiled HLO's ``op_name`` metadata, whatever the functions are
    called."""
    import re

    import jax

    from repro.core import AdaptiveGaussian, ModelBuilder
    from repro.core.gibbs import gibbs_step, init_state
    from repro.core.sparse import random_sparse

    mat, _, _ = random_sparse(3, (40, 24), 0.3, rank=3)
    b = ModelBuilder(num_latent=4)
    b.add_entity("u", 40)
    b.add_entity("v", 24)
    b.add_block("u", "v", mat, noise=AdaptiveGaussian())
    model, data, _ = b.build()
    state = init_state(model, data, 0)
    scopes = ["gather", "gram", "solve", "hyper", "noise", "metrics",
              "residuals"]
    if program == "gibbs_step":
        low = gibbs_step.lower(model, data, state)
    else:
        from repro.core.distributed import make_distributed_step
        from repro.launch.mesh import make_mesh
        step, ds, _ = make_distributed_step(
            model, make_mesh((1,), ("data",)), data, state,
            pipeline="eager")
        low = step.lower(jax.device_put(data, ds), state)
        scopes.append("exchange")
    names = set(re.findall(r'op_name="([^"]*)"', low.compile().as_text()))
    for scope in scopes:
        assert any(f"/{scope}/" in n for n in names), scope
