"""Interval program on the device: device busy milliseconds (union of
the operations on the chip, from the profiler trace) per simulated
interval of the chunks that ran inside the traced part of the window.
Moves ``tasks_per_s``."""


def read(run):
    p = run.get("profile")
    n = run["spans"].count("chunk", *run["traced"]) \
        * run["counts"]["chunk_intervals"]
    if not p or not n or p["busy_s"] <= 0:
        return None
    return p["busy_s"] * 1e3 / n
