"""Share of the traced window in which no operation ran on the device."""


def read(run):
    busy_s, window_s = run.busy()
    if window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
