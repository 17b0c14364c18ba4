"""The readers of the program's own spans, in traced runs of the
one-chip cells rehearsed tiny on the CPU (``rehearse.TINY``): each
prints its reading and its split by program span."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(str(tmp_path_factory.mktemp("bench")))


def test_traced_sweep_reads_the_host_loop_and_the_save_stall(root):
    rc, line, out = rehearse.run_cell(root, "bmf_chembl.sweep",
                                      seconds=0.5, trace=1)
    assert rc == 0 and line["correct"] is True
    m = line["metrics"]
    assert m["host_loop_ms.sweep"]["value"] > 0
    assert m["save_stall_ms"]["value"] > 0
    assert m["host_loop_ms.sweep"]["unit"] == "ms"
    assert "serve_host_ms.batch" not in m
    for span in ("session/readback", "session/accumulate", "session/save",
                 "ckpt/host_copy"):
        assert f"host_loop_ms.sweep {span}:" in out, span
    assert "save_stall_ms ckpt/wait:" in out


def test_traced_serving_reads_the_host_step(root):
    rc, line, out = rehearse.run_cell(root, "bmf_chembl.recommend_batch",
                                      seconds=0.5, trace=1)
    assert rc == 0 and line["correct"] is True
    m = line["metrics"]
    assert m["serve_host_ms.batch"]["value"] > 0
    assert "host_loop_ms.sweep" not in m and "save_stall_ms" not in m
    for span in ("predict/rows", "predict/mask", "predict/score",
                 "serve/finish"):
        assert f"serve_host_ms.batch {span}:" in out, span
