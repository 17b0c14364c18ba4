"""The trace reducer against numbers read by hand from a trace recorded
on one TPU v5e: two Gibbs sweeps of the bmf_chembl 1/32 share, each
inside a ``bench/sweep`` host span, with the compiled sweep's HLO text.

Read by hand from that trace (its "XLA Ops" line of /device:TPU:0 and
the host "python" line): the two spans cover 2.157312633 s from the
first's start to the second's end; the device ran 852 ops for
2.153060525 s, 0.000194338 s of it before the first span opened
(the first jit_gibbs_step program starts at 46,049,099 ns, the span at
46,243,437 ns); four custom calls, the batched Cholesky and triangular
inverse of 32,768 and of 8,192 rows, took 0.916326307, 0.66589209,
0.229124685 and 0.166474533 s.
"""
import gzip
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchkit import trace  # noqa: E402

DATA = os.path.join(BENCH, "testdata")
CUSTOM_CALLS = 0.916326307 + 0.66589209 + 0.229124685 + 0.166474533


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    path = d / "sweep.xplane.pb"
    with gzip.open(os.path.join(DATA, "sweep_v5e.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with gzip.open(os.path.join(
            DATA, "sweep_v5e.jit_gibbs_step.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    tables = {}
    for name in os.listdir(os.path.join(BENCH, "stages")):
        with open(os.path.join(BENCH, "stages", name)) as f:
            tables[name[:-5]] = json.load(f)
    stages = trace.Stages(tables, {"jit_gibbs_step": hlo})
    _, spans = trace.load(str(path))
    sweeps = [s for s in spans if s.name == "bench/sweep"]
    window = (sweeps[0].start, sweeps[-1].end)
    return trace.reduce(str(path), window, stages, spans), stages


def test_window_and_busy_time(recorded):
    red, _ = recorded
    assert red.window_s == pytest.approx(2.157312633, abs=1e-9)
    (dev,) = red.busy_s
    assert dev == "/device:TPU:0"
    assert red.busy_s[dev] == pytest.approx(2.153060525 - 0.000194338,
                                            abs=1e-4)


def test_stages_account_for_the_device_time(recorded):
    red, _ = recorded
    st = red.stage_s["/device:TPU:0"]
    assert sum(st.values()) == pytest.approx(
        red.busy_s["/device:TPU:0"], abs=1e-4)
    # the four custom calls carry no stack frame of their own: they are
    # the solve's through the operand built in _sample_normal_factor
    assert st["solve"] > CUSTOM_CALLS
    assert st["solve"] / sum(st.values()) > 0.9
    assert 0.02 < st["gram"] < 0.1
    assert st["noise"] > 0 and st["hyper"] < st["noise"]
    assert st.get("exchange", 0.0) == 0.0
    assert red.exposed_s["/device:TPU:0"] == 0.0


def test_custom_calls_resolve_to_the_solve(recorded):
    _, stages = recorded
    frames = stages.frames["jit_gibbs_step"]
    for name in ("custom-call.10", "custom-call.26", "custom-call.12",
                 "custom-call.34"):
        assert frames[name] == ("_sample_normal_factor",)
        assert stages.of(trace.Op(0, 1, name, "jit_gibbs_step")) == "solve"


def test_idle_gaps_fill_the_rest_of_the_window(recorded):
    red, _ = recorded
    idle = sum(s for _, s in red.gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s[
        "/device:TPU:0"], abs=1e-6)


def test_interval_arithmetic_by_hand():
    assert trace._union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert trace._clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]
    # collectives [0, 10) and [20, 30); compute [5, 25): 5 + 5 exposed
    assert trace._minus([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert trace._minus([(0, 10)], []) == 10
    assert trace._minus([(0, 10)], [(0, 10)]) == 0


def test_collectives_are_the_exchange():
    st = trace.Stages({})
    for name in ("all-gather-start.3", "all-gather-done.1", "all-reduce.7",
                 "collective-permute-done", "reduce-scatter.2"):
        assert st.of(trace.Op(0, 1, name, "jit_fn")) == "exchange"
    assert st.of(trace.Op(0, 1, "fusion.3", "jit_fn")) == "other"
