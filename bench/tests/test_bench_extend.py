"""A later change adds a cell with its own traffic kind, mix, limits and
per-layer metric as new files and new ``BENCHMARK.json`` entries only,
and the harness runs it: no file that is there is edited."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402

KIND = '''
import time
import jax.numpy as jnp


def run(run):
    from repro.core.predict import make_test_set, predict_one
    cfg = run.config
    u = jnp.ones((cfg["n_rows"], cfg["num_latent"]))
    v = jnp.ones((cfg["n_cols"], cfg["num_latent"]))
    test = make_test_set([0, 1], [0, 1], [0.0, 0.0])
    predict_one(u, v, test).block_until_ready()
    t0 = run.open_window()
    n = 0
    while time.perf_counter() - t0 < run.seconds:
        p = predict_one(u, v, test).block_until_ready()
        n += 1
    run.close_window()
    run.read_memory()
    run.attempted = n
    run.e2e["predict_s"] = run.window_s / n
    run.readings["calls"] = n
    run.check("predict_error", abs(float(p[0]) - cfg["num_latent"]))
'''

METRIC = '''
def read(run):
    return run.readings.get("calls")
'''


def test_a_new_cell_from_new_files(tmp_path):
    root = rehearse.tiny_root(str(tmp_path))
    bench = os.path.join(root, "bench")
    before = {}
    for d, _, files in os.walk(bench):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    with open(os.path.join(bench, "traffic", "spin.py"), "w") as f:
        f.write(KIND)
    with open(os.path.join(bench, "traffic", "spin_fast.json"), "w") as f:
        json.dump({"kind": "spin"}, f)
    with open(os.path.join(bench, "metrics", "predict_calls.py"), "w") as f:
        f.write(METRIC)
    with open(os.path.join(bench, "limits", "bmf_chembl.spin.json"),
              "w") as f:
        json.dump({"predict_error": 0.0}, f)
    path = os.path.join(root, "BENCHMARK.json")
    bm = json.load(open(path))
    bm["workloads"].append({"name": "bmf_chembl.spin", "config": "bmf_chembl",
                            "traffic": "spin_fast", "chips": 1,
                            "why": "a throwaway cell"})
    bm["end_to_end"].append({"name": "predict_s", "unit": "s",
                             "better": "lower", "bound": 0.05,
                             "source": "host_clock",
                             "workloads": ["bmf_chembl.spin"]})
    bm["per_layer"].append({"name": "predict_calls", "unit": "calls",
                            "better": "higher", "source": "host_clock",
                            "layer": "kernels", "moves": "predict_s",
                            "workloads": ["bmf_chembl.spin"]})
    json.dump(bm, open(path, "w"))

    rc, line, _ = rehearse.run_cell(root, "bmf_chembl.spin", seconds=0.3)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "predict_s"}
    rc, line, _ = rehearse.run_cell(root, "bmf_chembl.spin", seconds=0.3,
                                    trace=1)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"predict_calls"}
    assert line["metrics"]["predict_calls"]["value"] > 0
    for p, content in before.items():
        if "/.out/" not in p:
            assert open(p, "rb").read() == content, p
