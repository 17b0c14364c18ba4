"""Device time of the Macau link stage (centring, ``F^T U``, the (D, D)
factor and solves, beta's and lambda_beta's draws, the per-row prior
means) per sweep, mean over the chips used."""


def read(run):
    red, n = run.reduced, run.readings.get("traced_sweeps")
    per = [s.get("link", 0.0) for s in red.stage_s.values()]
    if not n or not any(per):
        return None
    for dev, s in sorted(red.stage_s.items()):
        print(f"link_ms {dev}: {1e3 * s.get('link', 0.0) / n}")
    return 1e3 * sum(per) / len(per) / n
