"""Plan engine: model FLOP/s utilization of the traced part of the
window, model FLOPs of the requests served in it (``bench/flops``: the
plan's forward and the fidelity forward, each request prorated by the
share of its wall inside the traced interval) over the traced seconds
times the chip's bf16 peak.  Moves ``tasks_per_s``."""


def read(run):
    c = run["counts"]
    lo, hi = run.get("traced") or (None, None)
    if lo is None or hi is None or hi <= lo or not c.get("peak_flops"):
        return None
    done = 0.0
    for s, e, fl in c["requests"]:
        inside = min(e, hi) - max(s, lo)
        if inside > 0 and e > s:
            done += fl * inside / (e - s)
    return 100.0 * done / ((hi - lo) * c["peak_flops"])
