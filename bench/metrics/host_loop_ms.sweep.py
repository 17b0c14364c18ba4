"""Device-idle time per sweep while the loop thread is in
``Session.run``'s ``sweep``, ``session/*`` or ``ckpt/*`` spans, mean
over the chips used; printed by the innermost program span."""
from benchkit import spans


def _loop(name):
    return name == "sweep" or name.startswith(("session/", "ckpt/"))


def read(run):
    n = run.readings.get("traced_sweeps")
    got = spans.of(run).idle(_loop)
    if not n or got is None:
        return None
    idle, by = got
    for name, s in sorted(by.items(), key=lambda kv: -kv[1]):
        print(f"host_loop_ms.sweep {name}: {1e3 * s / n} ms per sweep")
    return 1e3 * idle / n
