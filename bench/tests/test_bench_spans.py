"""The program-span reader on a hand-built trace: a loop thread with
nested spans, a writer thread whose span overlaps them, JAX's own
dispatch span, and device ops, with the idle time under each span
worked out by hand (times in ns)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchkit import spans  # noqa: E402
from benchkit.trace import Span  # noqa: E402

LOOP = [Span(0, 100, "bench.window"), Span(10, 40, "sweep"),
        Span(12, 14, "PjitFunction(gibbs_step)"),
        Span(30, 40, "session/readback"), Span(40, 60, "session/save"),
        Span(40, 45, "ckpt/wait"), Span(45, 55, "ckpt/host_copy"),
        Span(50, 52, "gc"), Span(60, 70, "session/callbacks"),
        Span(80, 90, "bench.other")]
WRITER = [Span(45, 90, "ckpt/save")]
OPS = [(0, 35), (41, 44), (58, 65)]


def _loop(name):
    return name == "sweep" or name.startswith(("session/", "ckpt/"))


def test_idle_under_each_innermost_program_span():
    prog = spans.Program([WRITER, LOOP], {"chip0": OPS})
    idle, by = prog.idle(_loop)
    # [10, 70) is 60 ns, 35 of them busy
    assert idle == pytest.approx(25e-9)
    assert by == pytest.approx({
        "sweep": 0.0, "session/readback": 5e-9, "ckpt/wait": 2e-9,
        "ckpt/host_copy": 8e-9, "gc": 2e-9, "session/save": 3e-9,
        "session/callbacks": 5e-9})
    assert prog.window_idle_s() == pytest.approx(55e-9)


def test_idle_is_the_mean_over_devices():
    prog = spans.Program([LOOP, WRITER], {"chip0": OPS, "chip1": []})
    idle, by = prog.idle(_loop)
    assert idle == pytest.approx((25e-9 + 60e-9) / 2)
    assert by["session/callbacks"] == pytest.approx((5e-9 + 10e-9) / 2)


def test_durations_and_counts_keep_to_the_loop_thread():
    prog = spans.Program([WRITER, LOOP], {"chip0": OPS})
    assert prog.duration(("ckpt/wait", "ckpt/host_copy")) == \
        pytest.approx(15e-9)
    assert prog.duration(("ckpt/save",)) == 0.0
    assert prog.count("sweep") == 1 and prog.count("ckpt/save") == 0


def test_no_program_span_reads_nothing():
    """A program without the spans (an older commit) gives no reading
    and raises nothing."""
    bare = [Span(0, 100, "bench.window"), Span(10, 40, "PjitFunction(f)")]
    prog = spans.Program([bare], {"chip0": OPS})
    assert prog.idle(_loop) is None
    assert prog.count("serve/step") == 0


def test_leaves_name_the_innermost_span():
    got = spans.leaves([Span(0, 10, "a"), Span(2, 5, "b"),
                        Span(5, 7, "c"), Span(12, 14, "d")])
    assert got == [(0, 2, "a"), (2, 5, "b"), (5, 7, "c"), (7, 10, "a"),
                   (12, 14, "d")]
