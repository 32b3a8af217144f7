"""Device, served stream: share of the traced window in which no
operation ran on the chip.  Moves ``tasks_per_s``."""


def read(run):
    p = run.get("profile")
    if not p or p["window_s"] <= 0 or p["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
