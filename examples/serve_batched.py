"""Batched serving demo: continuous batching over decode slots.

Initializes a small model, submits a handful of prompt requests, and
drives the ``BatchedServer`` runtime (prefill-through-decode path with
a KV cache per slot) until all complete.

    PYTHONPATH=src python examples/serve_batched.py
"""
import numpy as np
import jax

from repro.configs import get_smoke
from repro.launch.serve import BatchedServer
from repro.models import init_model
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = get_smoke("qwen3_4b")
    print(f"serving {cfg.name}: {cfg.n_layers}L d={cfg.d_model}")
    params = init_model(jax.random.PRNGKey(0), cfg)

    server = BatchedServer(cfg, params, slots=4, max_len=64)
    rng = np.random.default_rng(0)
    for r in range(6):       # more requests than slots -> queueing
        plen = int(rng.integers(4, 12))
        server.submit(rng.integers(0, cfg.vocab_size, size=plen),
                      max_new=12, req_id=f"req{r}")

    done = server.run()
    for req in done:
        print(f"  {req['id']}: prompt[{len(req['prompt'])}] -> "
              f"{req['generated']}")
    print(f"{len(done)} requests completed")


if __name__ == "__main__":
    main()
