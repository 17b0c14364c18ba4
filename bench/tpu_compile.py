"""Compile each cell's programs at their real sizes for a described v5e.

    JAX_PLATFORMS=cpu python bench/tpu_compile.py [cell ...]

Run by hand before a chip call: the TPU compiler installed here refuses
what the chip would refuse (layouts, memory), at no chip time.  For
each sweep cell it compiles the sweep that ``Session`` runs (on one
chip, or on a ("data", 4) mesh of a v5e:2x2), and for each serving
cell the scorer at every batch size the slots can form, and prints each
program's memory.  Nothing runs, so it says nothing about time.
"""
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402

from benchkit.data import fixed_degree  # noqa: E402


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def _report(name, compiled):
    m = compiled.memory_analysis()
    print(json.dumps({"program": name,
                      "argument_bytes": m.argument_size_in_bytes,
                      "temp_bytes": m.temp_size_in_bytes,
                      "output_bytes": m.output_size_in_bytes}), flush=True)


def sweep(cfg, devices):
    from repro.core import AdaptiveGaussian, ModelBuilder, from_coo
    from repro.core.distributed import make_distributed_step
    from repro.core.gibbs import gibbs_step, init_state
    prob = fixed_degree(0, cfg["n_rows"], cfg["n_cols"], cfg["nnz_per_row"],
                        cfg["n_test_per_row"])
    b = ModelBuilder(num_latent=cfg["num_latent"])
    b.add_entity("compound", cfg["n_rows"])
    b.add_entity("protein", cfg["n_cols"])
    b.add_block("compound", "protein",
                from_coo(prob.i, prob.j, prob.v, prob.shape),
                noise=AdaptiveGaussian())
    model, data, _ = b.build()
    state = jax.eval_shape(lambda: init_state(model, data, 0))
    if len(devices) == 1:
        one = SingleDeviceSharding(devices[0])
        on = lambda t: _abstract(t, jax.tree.map(lambda _: one, t))  # noqa: E731
        _report("gibbs_step", gibbs_step.lower(
            model, on(data), on(state)).compile())
        return
    mesh = Mesh(devices, ("data",))
    step, ds, ss = make_distributed_step(model, mesh, data, state,
                                         pipeline=cfg.get("pipeline"))
    _report(f"sharded sweep on {len(devices)} chips", step.lower(
        _abstract(data, ds), _abstract(state, ss)).compile())


def serve(cfg, devices):
    from repro.kernels import ops
    one = SingleDeviceSharding(devices[0])
    s, n, k = cfg["samples"], cfg["n_cols"], cfg["num_latent"]
    v = jax.ShapeDtypeStruct((s, n, k), jnp.float32, sharding=one)
    for batch in range(1, cfg["slots"] + 1):
        us = jax.ShapeDtypeStruct((batch, s, k), jnp.float32, sharding=one)
        ex = jax.ShapeDtypeStruct((batch, n), jnp.float32, sharding=one)
        _report(f"topk_score B={batch}", jax.jit(
            lambda u, w, e: ops.topk_score(u, w, cfg["top_k"], exclude=e)
        ).lower(us, v, ex).compile())


def main(cells):
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    configs = {c["name"]: c for c in bm["configs"]}
    for cell in bm["workloads"]:
        if cells and cell["name"] not in cells:
            continue
        cfg = json.load(open(os.path.join(ROOT,
                                          configs[cell["config"]]["file"])))
        kind = json.load(open(os.path.join(
            BENCH, "traffic", cell["traffic"] + ".json")))["kind"]
        print(f"cell {cell['name']}", flush=True)
        devices = list(topo.devices[:cell["chips"]])
        (sweep if kind == "sweep" else serve)(cfg, devices)


if __name__ == "__main__":
    main(sys.argv[1:])
