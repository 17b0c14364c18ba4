"""Device time of the conditional draw (batched Cholesky, triangular
solves, normals) per sweep, mean over the chips used."""


def read(run):
    red, n = run.reduced, run.readings.get("traced_sweeps")
    per = [s.get("solve", 0.0) for s in red.stage_s.values()]
    if not n or not any(per):
        return None
    for dev, s in sorted(red.stage_s.items()):
        print(f"solve_ms {dev}: {1e3 * s.get('solve', 0.0) / n}")
    return 1e3 * sum(per) / len(per) / n
