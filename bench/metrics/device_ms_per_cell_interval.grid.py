"""Batched grid program on the devices: device busy milliseconds,
summed over the chips, per grid cell-interval of the calls that ran
inside the traced part of the window.  Moves ``cell_intervals_per_s``."""


def read(run):
    p = run.get("profile")
    n = run["spans"].count("grid_call", *run["traced"]) \
        * run["counts"]["cell_intervals_per_call"]
    if not p or not n or p["busy_s"] <= 0:
        return None
    return sum(p["busy_by_device"].values()) * 1e3 / n
