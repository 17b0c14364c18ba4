"""Device-idle time per service step while the server's thread is in
its own ``serve/admit`` or ``serve/step`` span, mean over the window's
steps; printed by the innermost program span (``predict/*``,
``serve/*``, ``gc``) and as a share of the window's idle time."""
from benchkit import spans


def read(run):
    prog = spans.of(run)
    steps = prog.count("serve/step")
    got = prog.idle(lambda n: n in ("serve/admit", "serve/step"))
    if not steps or got is None:
        return None
    idle, by = got
    for name, s in sorted(by.items(), key=lambda kv: -kv[1]):
        print(f"serve_host_ms.batch {name}: {1e3 * s / steps} ms per step")
    print(f"serve_host_ms.batch: {steps} steps, {idle} s idle in them of "
          f"{prog.window_idle_s()} s idle in the window")
    return 1e3 * idle / steps
