"""The harness: one cell, one run, one result line.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix.  Everything that belongs to one of
them is data or code of its own, found by name:

* ``bench/configs/<config>.json``: the deployment's sizes, its source,
  what was reduced and assumed;
* ``bench/traffic/<mix>.json``: the mix's parameters, with ``kind``
  naming the module ``bench/traffic/<kind>.py`` that generates it and
  drives the program;
* ``bench/limits/<cell>.json``: the limit of each number that the
  comparison with the reference holds the cell to;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
* ``bench/stages/<stage>.json``: the programs and functions whose
  device ops make up one stage of the trace.

A traffic kind's ``run(run)`` sets up the program, calls ``run.open_window()`` when set-up
is over, measures for ``run.seconds``, calls ``run.close_window()``,
reads ``run.read_memory()``, frees the program's state, compares with
the reference through ``run.check`` and fills ``run.e2e``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ChipMissing(RuntimeError):
    pass


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Run:
    """What one run of one cell knows and records."""

    def __init__(self, root: str, benchmark: dict, cell: dict, seed: int,
                 seconds: float, trace: bool, t_start: float):
        self.root = root
        self.bench = os.path.join(root, "bench")
        self.name = cell["name"]
        conf = next(c for c in benchmark["configs"]
                    if c["name"] == cell["config"])
        self.config = _json(os.path.join(root, conf["file"]))
        self.mix = _json(os.path.join(self.bench, "traffic",
                                      cell["traffic"] + ".json"))
        limits = os.path.join(self.bench, "limits", self.name + ".json")
        self.limits = _json(limits) if os.path.exists(limits) else {}
        self.chips = int(cell["chips"])
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.out = os.path.join(self.bench, ".out", self.name)
        os.makedirs(self.out, exist_ok=True)
        self.e2e: Dict[str, float] = {}
        self.readings: Dict[str, Any] = {}
        self.checks: Dict[str, Dict[str, float]] = {}
        self.attempted = 0
        self.failed = 0
        self.setup_s: Optional[float] = None
        self.window: Optional[tuple] = None
        self.memory_peak: Optional[int] = None
        self.hlo: Dict[str, str] = {}
        self._annotation = None
        self._trace_dir = os.path.join(self.out, "trace")
        self.devices: List[Any] = []
        self._t0 = None
        self._phases: List[tuple] = []
        self.compiles_in_window = 0

    # -- window ------------------------------------------------------

    def phase(self, name: str) -> None:
        """Mark the end of a phase of set-up (printed at the window's
        opening, with each phase's seconds)."""
        self._phases.append((name, time.perf_counter()))

    def _on_event(self, event, *args, **kwargs):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            if self._t0 is not None and self.window is None:
                self.compiles_in_window += 1

    def open_window(self) -> float:
        """Set-up is over: start the clock (and the trace).

        Set-up's garbage is collected first, as the last step of
        set-up; the collector stays on inside the window.
        """
        import jax
        gc.collect()
        if self.trace:
            import shutil
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._trace_dir,
                                     profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()
        # anything that compiles inside the window is named on stderr
        jax.config.update("jax_log_compiles", True)
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        self._t0 = now
        marks = [("start", self.t_start)] + self._phases + [("warm-up", now)]
        print("set-up phases (s): " + ", ".join(
            f"{b[0]} {b[1] - a[1]:.3f}" for a, b in zip(marks, marks[1:])),
            flush=True)
        return now

    def close_window(self) -> float:
        now = time.perf_counter()
        self.window = (self._t0, now)
        import jax
        jax.config.update("jax_log_compiles", False)
        if self.trace:
            import jax
            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return now

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def span(self, name: str):
        """A host span in the trace around the harness's call into a
        layer (no-op when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def read_memory(self) -> None:
        """The peak on the fullest chip: the allocator's peak in use plus
        its peak reserved for the programs' temporaries, which the TPU
        runtime holds apart from ``bytes_in_use``."""
        stats = [d.memory_stats() or {} for d in self.devices]
        for d, s in zip(self.devices, stats):
            print(f"memory_stats {d}: {s}", flush=True)
        self.memory_peak = max(int(s.get("peak_bytes_in_use", 0))
                               + int(s.get("peak_bytes_reserved", 0))
                               for s in stats)

    def check(self, name: str, value: float) -> None:
        """One reading of the comparison with the reference: compared
        against its limit where ``bench/limits/<cell>.json`` gives one,
        else only printed."""
        if name not in self.limits:
            print(f"reading {name}: {value} (not compared)", flush=True)
            return
        self.checks[name] = {"value": float(value),
                             "limit": float(self.limits[name])}

    @property
    def correct(self) -> bool:
        return set(self.checks) == set(self.limits) and all(
            c["value"] <= c["limit"] for c in self.checks.values())

    # -- per-layer metrics --------------------------------------------

    def stage_tables(self) -> Dict[str, dict]:
        d = os.path.join(self.bench, "stages")
        return {f[:-5]: _json(os.path.join(d, f))
                for f in sorted(os.listdir(d)) if f.endswith(".json")}

    def reduce_trace(self):
        from . import trace
        path = trace.find_xplane(self._trace_dir)
        for mod, text in self.hlo.items():
            with open(os.path.join(self._trace_dir, mod + ".hlo.txt"),
                      "w") as f:
                f.write(text)
        _, spans = trace.load(path)
        window = trace.bench_window(spans, "bench.window")
        self.reduced = trace.reduce(
            path, window, trace.Stages(self.stage_tables(), self.hlo),
            spans)
        return self.reduced


def _metrics_for(benchmark: dict, cell: str, kind: str,
                 reported=()) -> List[dict]:
    out = []
    for m in benchmark[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m.get("moves") in reported:
            out.append(m)
    return out


def device_check(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise ChipMissing(f"needs a TPU, but JAX's first device is on "
                          f"platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise ChipMissing(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devs)}")
    return devs[:chips]


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed directory inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, "bench", ".out", "jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t_start: Optional[float] = None,
         root: Optional[str] = None, require_chip: bool = True) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    root = root or os.path.dirname(BENCH)
    benchmark = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in benchmark["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; cells: "
              f"{', '.join(sorted(cells))}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    run = Run(root, benchmark, cell, args.seed, args.seconds,
              bool(args.trace), t_start)
    import jax
    try:
        run.devices = (device_check(run.chips) if require_chip
                       else jax.devices()[:run.chips])
    except ChipMissing as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    enable_cache(root)
    jax.monitoring.register_event_listener(run._on_event)
    kind = load_module(
        os.path.join(run.bench, "traffic", run.mix["kind"] + ".py"),
        "bench_traffic_" + run.mix["kind"])
    kind.run(run)
    gc.collect()
    print(f"bench: {run.compiles_in_window} programs compiled or loaded "
          f"inside the window", flush=True)

    e2e = _metrics_for(benchmark, run.name, "end_to_end")
    run.e2e["setup_s"] = run.setup_s
    metrics = {}
    if not args.trace:
        for m in e2e:
            if m["name"] not in run.e2e:
                raise RuntimeError(f"the traffic kind {run.mix['kind']} "
                                   f"reported no {m['name']}")
            metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                  "unit": m["unit"]}
    d0 = run.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.memory_peak}
    line: Dict[str, Any] = {"correct": run.correct,
                            "attempted": run.attempted,
                            "failed": run.failed}
    if args.trace:
        red = run.reduce_trace()
        reported = {m["name"] for m in e2e}
        for m in _metrics_for(benchmark, run.name, "per_layer", reported):
            reader = load_module(
                os.path.join(run.bench, "metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = sum(red.busy_s.values()) / len(red.busy_s)
        device["window_s"] = red.window_s
        for dev in sorted(red.busy_s):
            print(f"trace {dev}: busy {red.busy_s[dev]} s of "
                  f"{red.window_s} s, stages {red.stage_s[dev]}, "
                  f"exposed collectives {red.exposed_s[dev]} s",
                  flush=True)
        first = sorted(red.stage_s)[0]
        ops = sorted(red.stage_s[first].items(), key=lambda kv: -kv[1])
        line["breakdown"] = {"device_ops": [[k, v] for k, v in ops[:10]],
                             "idle_gaps": [[k, v] for k, v in
                                           red.gaps[:10]]}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = run.checks
    for name, c in run.checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
