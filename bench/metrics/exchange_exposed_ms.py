"""Collective device time per sweep during which no compute runs on
that device; the highest of the chips, each printed."""


def read(run):
    red, n = run.reduced, run.readings.get("traced_sweeps")
    if not n or run.chips < 2:
        return None
    for dev, s in sorted(red.exposed_s.items()):
        print(f"exchange_exposed_ms {dev}: {1e3 * s / n}")
    return 1e3 * max(red.exposed_s.values()) / n
