"""Reduce a profiler trace of one window to device busy and idle time,
the device operations that took most time, and the idle gaps by the
harness span that was open.

``load`` reads a ``.xplane.pb`` with JAX's own reader: on each TPU
plane the program executions (``XLA Modules``), which give busy time,
and the outermost operations (``XLA Ops``), which give the top
operations; and the harness's ``bench.<span>`` annotations from the
host planes.  ``reduce`` is plain interval arithmetic on those lists,
so it can be checked on a hand-made or CPU-recorded trace.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(path: str, is_device=lambda plane: plane.startswith("/device:TPU:"),
         is_busy=lambda line: line == "XLA Modules",
         is_ops=lambda line: line == "XLA Ops"):
    """({device plane: (busy intervals, [(op, start_ns, end_ns)])},
    [(span, start_ns, end_ns)]) from one trace file.

    Busy intervals are the program executions on the lines ``is_busy``
    picks; the operations are the outermost events of the lines
    ``is_ops`` picks (an operation inside a loop body lies inside the
    loop's own event and is not counted twice).  Spans are every
    ``bench.`` annotation, with the host line (thread) it was on."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        lines = list(plane.lines)
        if is_device(plane.name):
            busy, ops = [], []
            for ln in lines:
                if is_busy(ln.name):
                    busy += [(e.start_ns, e.start_ns + e.duration_ns)
                             for e in ln.events if e.duration_ns > 0]
                if is_ops(ln.name):
                    outer_end = float("-inf")
                    for e in ln.events:
                        end = e.start_ns + e.duration_ns
                        if e.duration_ns > 0 and e.start_ns >= outer_end:
                            ops.append((op_name(e.name), e.start_ns, end))
                            outer_end = end
            devices[plane.name] = (busy, ops)
        for i, ln in enumerate(lines):
            for e in ln.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  (plane.name, i)))
    return devices, spans


def union(intervals, lo, hi):
    """Disjoint sorted cover of ``intervals`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(cover, lo, hi):
    out, t = [], lo
    for s, e in cover:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(spans, s, e):
    """The innermost span open at the gap's middle, else ``outside``."""
    mid = (s + e) / 2
    open_ = [(ss, name) for name, ss, se in spans if ss <= mid < se]
    return max(open_)[1] if open_ else "outside"


def reduce(devices, spans, window, top=10) -> dict:
    """Busy seconds (mean over the devices), window seconds, per-device
    busy seconds, and the breakdown: top device operations by seconds
    (mean over the devices) and idle seconds by the harness span the
    window's thread had open (mean over the devices), each at most
    ``top`` entries.  ``window`` is what ``window_of`` returns."""
    (lo, hi), thread = window
    # what the dispatching thread (the one that opened the window) was
    # doing; the feeder thread's spans overlap it and say nothing of why
    # the device waited
    spans = [(n_, s, e) for n_, s, e, th in spans if th == thread]
    n = max(1, len(devices))
    busy, ops, idle = {}, defaultdict(float), defaultdict(float)
    for dev, (runs, evs) in devices.items():
        cover = union(runs, lo, hi)
        busy[dev] = sum(e - s for s, e in cover) * 1e-9
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops[name] += d * 1e-9 / n
        for s, e in gaps(cover, lo, hi):
            idle[label(spans, s, e)] += (e - s) * 1e-9 / n
    rank = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy.values()) / n, "window_s": (hi - lo) * 1e-9,
            "busy_by_device": busy,
            "breakdown": {"device_ops": rank(ops), "idle_gaps": rank(idle)}}


def window_of(spans, name="window"):
    """((start, end), thread) of the harness's ``window`` annotation."""
    w = [((s, e), th) for n, s, e, th in spans if n == name]
    if not w:
        raise ValueError("the trace holds no bench.window annotation")
    return w[0]
