"""``device_idle_pct``: 1 minus the union of the device's busy intervals
over the traced window, averaged over the chips used (the v5e device
layer). Moves solve_s."""


def read(run):
    red = run.trace
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
