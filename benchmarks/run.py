"""Benchmark driver — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only SECTION]

Sections (paper analogue):
    bmf_impls      Fig. 3  implementation comparison
    scaling        Fig. 3  worker-count scaling (subprocess devices)
    platform_sweep Fig. 4  data-type sweep (sparse/dense/side-info)
    compile_modes  Fig. 5  dispatch/compile modes
    gfa            §4      GFA simulated-study reproduction
    macau          §4      Macau side-info lift (incl. cold start)
    roofline       §5      roofline summary from the dry-run records
    serving        §1      RecommendServer latency/QPS under load

Output: CSV rows ``section,name,value,unit,notes``.
"""
from __future__ import annotations

import argparse
import time

from .common import emit
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes / fewer sweeps")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    q = args.quick

    print("section,name,value,unit,notes", flush=True)
    t0 = time.perf_counter()

    def want(name: str) -> bool:
        return args.only is None or args.only == name

    if want("bmf_impls"):
        from . import bmf_impls
        bmf_impls.run(*((600, 96) if q else (2000, 200)))
    if want("platform_sweep"):
        from . import platform_sweep
        platform_sweep.run(*((600, 96) if q else (2000, 200)))
    if want("compile_modes"):
        from . import compile_modes
        compile_modes.run(*((400, 64) if q else (1000, 128)))
    if want("gfa"):
        from . import gfa_repro
        gfa_repro.run(quick=q)
    if want("macau"):
        from . import macau_lift
        macau_lift.run(*((500, 64, 60, 60) if q else (1500, 120, 120, 120)))
    if want("scaling"):
        from . import scaling
        scaling.run((1, 2, 4) if q else (1, 2, 4, 8))
    if want("roofline"):
        from . import roofline_table
        roofline_table.run()
    if want("serving"):
        from . import serve_latency
        serve_latency.run(quick=q)

    emit("meta", "total_runtime", f"{time.perf_counter() - t0:.1f}",
         "s", "benchmarks.run wall time")


if __name__ == "__main__":
    main()
