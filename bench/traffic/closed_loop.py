"""Traffic kind ``closed_loop``: a pipeline with a fixed number of
workers profiling a compound library.

Each of ``clients`` workers sends its next request (a compound drawn
uniformly, top ``top_k`` proteins, its measured ones excluded) as soon
as its previous answer arrives, so with more clients than slots every
slot stays full.  ``recommend_rps`` is the answers completed in the
window over its length; the window closes when the first service
step that ends past ``run.seconds`` returns.  Requests still open then
are answered after it, for the comparison, and not counted.  Each
answer's time is read on the benchmark's clock when the server's step
that produced it returns.
"""
from __future__ import annotations

import time

import numpy as np

from benchkit import serving


def run(run):
    served = serving.Served(run)
    srv = served.server
    clients = int(run.mix["clients"])
    rng = np.random.default_rng(run.seed)
    owner, user_of, sent, nxt = {}, {}, {}, [0]

    def send(client):
        u = int(rng.integers(0, run.config["n_rows"]))
        rid = served.submit(u, req_id=f"q{nxt[0]}")
        owner[rid], user_of[rid] = client, u
        sent[rid] = time.perf_counter()
        nxt[0] += 1

    for c in range(clients):
        send(c)
    t0 = run.open_window()
    end = t0 + run.seconds
    lat = []
    seen = 0
    while True:
        now = served.step()
        new, seen = srv.done[seen:], len(srv.done)
        lat += [now - sent[r["id"]] for r in new]
        if now >= end:
            break
        for r in new:
            send(owner[r["id"]])
    run.close_window()
    served.readings()
    srv.run()                                # answer what is still open
    answers = [(user_of[r["id"]], r["ids"], r["mean"], r["std"])
               for r in srv.done]
    run.attempted = nxt[0]
    run.failed = nxt[0] - len(srv.done)
    run.e2e["recommend_rps"] = len(lat) / run.window_s
    print(f"closed_loop: {clients} clients, {len(lat)} answers in "
          f"{run.window_s} s; latency p50 {1e3 * np.median(lat)} ms",
          flush=True)
    run.read_memory()
    served.close()
    serving.check(run, answers)
