"""Readings of the comparison with the reference, for setting limits.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 \
        [--control-seeds 1,2] [--factors 0]

Runs the cell once per seed in this one process (compiled programs are
shared, so only the first run pays set-up in full), and prints, per
seed, the numbers the comparison reads for the program and, on the
seeds of ``--control-seeds`` (all by default), for each control (the
reference in a lower precision in the program's place) and each fault
that the traffic kind plants.  A limit lies between the program's
largest reading and the smallest of the controls' and faults'.
``--factors 0`` leaves the factor draws out of a sweep cell's readings.
Also writes them to ``bench/.out/calibrate_<cell>.jsonl``.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchkit import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control-seeds", default=None)
    ap.add_argument("--factors", type=int, default=1)
    args = ap.parse_args(argv)
    root = os.path.dirname(harness.BENCH)
    sys.path.insert(0, os.path.join(root, "src"))
    bm = json.load(open(os.path.join(root, "BENCHMARK.json")))
    cell = next(c for c in bm["workloads"] if c["name"] == args.workload)
    import jax
    devices = harness.device_check(int(cell["chips"]))
    harness.enable_cache(root)
    out = os.path.join(root, "bench", ".out",
                       f"calibrate_{args.workload}.jsonl")
    seeds = [int(s) for s in args.seeds.split(",")]
    controlled = set(seeds if args.control_seeds is None else
                     [int(s) for s in args.control_seeds.split(",")])
    for seed in seeds:
        run = harness.Run(root, bm, cell, seed, args.seconds, False,
                          time.perf_counter())
        run.limits = {}
        run.check = lambda name, value: None
        run.devices = devices
        run.factors = bool(args.factors)
        kind = harness.load_module(
            os.path.join(run.bench, "traffic", run.mix["kind"] + ".py"),
            "bench_traffic_" + run.mix["kind"])
        kind.run(run)
        rec = {"seed": seed, "setup_s": run.setup_s, "e2e": run.e2e,
               "program": run.readings.get("compared")}
        if seed in controlled:
            rec["controls"] = {k: f() for k, f in run.controls.items()}
            if hasattr(run, "faults"):
                rec["faults"] = run.faults()
        print("calibrate " + json.dumps(rec), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        del run
    print(jax.devices()[0].device_kind)


if __name__ == "__main__":
    main()
