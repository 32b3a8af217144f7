"""Devices, grid: share of the traced window in which no operation ran,
averaged over the chips.  Moves ``cell_intervals_per_s``."""


def read(run):
    p = run.get("profile")
    if not p or p["window_s"] <= 0 or p["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
