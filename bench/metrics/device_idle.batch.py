"""Device idle share of the traced window, mean over the chips used:
1 - busy / window, busy being the union of the device's op intervals."""


def read(run):
    red = run.reduced
    if not red.busy_s or red.window_s <= 0:
        return None
    busy = sum(red.busy_s.values()) / len(red.busy_s)
    return 100.0 * (1.0 - busy / red.window_s)
