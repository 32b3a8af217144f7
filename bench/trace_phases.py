"""Attribute a profiler trace of the served window to the interval
program's phases, and the device's idle time to the program's own host
spans.

Two passes beside ``trace_reduce``'s, which they leave as it is:

- ``phases``: the device time of every leaf operation (an operation
  event with no other inside it on its line: inside the chunk loop's
  event, the operations of its body, never the loop and its children
  both) by the phase scope it belongs to, the program's
  ``jax.named_scope`` of that hook (``PHASES``).  A leaf in no phase
  (loop control, carry copies, transfer conversions) and busy time no
  leaf covers are ``unscoped``.  Where the leaves cover under
  ``COVERAGE_MIN`` of the loop operations' time the profiler dropped
  events, and the split is ``None``: a dropped event would read as a
  fast phase.
- ``idle_by_span``: each idle gap of the device cut at the edges of the
  ``repro.`` spans (``RunLedger(annotate=True)``) open on the
  dispatching thread, each piece labelled by the innermost one, else
  ``outside``.

``load`` reads every operation event, nested ones included, with its
HLO module and operation; ``op_scopes`` reads the scope of each
operation from the compiled program's HLO text
(``metadata={op_name=...}``): neither a TPU's nor the CPU's operation
events carry it.  The rest is interval arithmetic on plain lists,
checked on hand-made and CPU-recorded traces.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench import trace_reduce

#: the phase scopes of the interval program, in hook order
PHASES = ("decide", "admit", "place", "apply", "substeps", "feedback",
          "telemetry")

#: prefix of the program's own span annotations
PROGRAM_PREFIX = "repro."

#: least share of the loop operations' time that leaf events must cover
#: for the phase split to be read
COVERAGE_MIN = 0.9

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)


def module_name(name) -> str:
    """``jit_run_chunk(99)`` -> ``jit_run_chunk``."""
    return re.sub(r"\(\d+\)$", "", str(name or ""))


def op_scopes(hlo_text: str) -> dict:
    """{(module, operation): scope path} from a compiled program's HLO
    text."""
    m = _MODULE.search(hlo_text)
    module = module_name(m.group(1)) if m else ""
    out = {}
    for line in hlo_text.splitlines():
        hit = _INSTR.match(line)
        if hit:
            out[(module, hit.group(1))] = hit.group(2)
    return out


def phase_of(scope, phases=PHASES):
    """The outermost phase scope on an operation's scope path, else
    None."""
    for part in (scope or "").split("/"):
        if part in phases:
            return part
    return None


def load(path, is_device=lambda plane: plane.startswith("/device:TPU:"),
         is_busy=lambda line: line == "XLA Modules",
         is_ops=lambda line: line == "XLA Ops"):
    """({device plane: (busy intervals, [(module, op, start_ns, end_ns,
    line)])}, [(span, start_ns, end_ns, thread)]) from one trace file.

    Unlike ``trace_reduce.load`` every operation event is kept, nested
    ones too.  An operation's module is its ``hlo_module`` stat, else
    the program execution (on the ``is_busy`` lines) it runs in: a
    TPU's operation events carry no module, only the instruction's HLO
    text as their name.  Spans are the harness's ``bench.`` annotations,
    named without the prefix as ``trace_reduce`` names them, and the
    program's ``repro.`` ones, named with it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        lines = list(plane.lines)
        if is_device(plane.name):
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           module_name(e.name))
                          for ln in lines if is_busy(ln.name)
                          for e in ln.events if e.duration_ns > 0)
            ops = []
            for i, ln in enumerate(lines):
                if not is_ops(ln.name):
                    continue
                for e in ln.events:
                    if e.duration_ns <= 0:
                        continue
                    st = dict(e.stats)
                    if "hlo_op" not in st and "hlo_module" not in st \
                            and not ln.name.startswith("XLA"):
                        continue            # a host region, not an op
                    module = module_name(st.get("hlo_module")) \
                        or module_at(runs, e.start_ns)
                    ops.append((module, str(st.get("hlo_op") or
                                            trace_reduce.op_name(e.name)),
                                e.start_ns, e.start_ns + e.duration_ns, i))
            devices[plane.name] = ([(s, e) for s, e, _ in runs], ops)
        for i, ln in enumerate(lines):
            for e in ln.events:
                for pre, keep in ((trace_reduce.SPAN_PREFIX, False),
                                  (PROGRAM_PREFIX, True)):
                    if e.name.startswith(pre):
                        spans.append((e.name if keep else e.name[len(pre):],
                                      e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      (plane.name, i)))
    return devices, spans


def module_at(runs, t):
    """The module of the execution in ``runs`` ((start, end, module),
    sorted) that holds time ``t``, else ``""``."""
    i = bisect.bisect_right(runs, (t, float("inf"), "")) - 1
    return runs[i][2] if i >= 0 and runs[i][0] <= t < runs[i][1] else ""


def nest(events):
    """For events of one line as (start, end), sorted by start then
    longest first: (root index, has children) of each."""
    roots, kids, stack = [], [False] * len(events), []
    for i, (s, e) in enumerate(events):
        while stack and not (s >= events[stack[-1]][0]
                             and e <= events[stack[-1]][1]):
            stack.pop()
        if stack:
            kids[stack[-1]] = True
        roots.append(stack[0] if stack else i)
        stack.append(i)
    return roots, kids


def phases(devices, window, scopes, phases=PHASES, top=10):
    """Seconds of device time by phase in the window (mean over the
    devices), with ``unscoped`` (busy time in no phase), the leaves'
    ``coverage`` of the loop operations, the busy seconds, and the
    leaf operations in no phase that took most time.  ``by_phase`` is
    None where the coverage is under ``COVERAGE_MIN``.  ``scopes`` is
    what ``op_scopes`` returns."""
    (lo, hi), _ = window
    n = max(1, len(devices))
    by, unscoped_ops = defaultdict(int), defaultdict(int)
    busy = loop = leaf_in_loop = 0          # nanoseconds
    for runs, ops in devices.values():
        busy += sum(e - s for s, e in trace_reduce.union(runs, lo, hi))
        per_line = defaultdict(list)
        for op in ops:
            per_line[op[4]].append(op)
        for evs in per_line.values():
            evs.sort(key=lambda o: (o[2], -o[3]))
            roots, kids = nest([(o[2], o[3]) for o in evs])
            for i, (module, name, s, e, _) in enumerate(evs):
                d = max(0, min(e, hi) - max(s, lo))
                if kids[i]:
                    if roots[i] == i:
                        loop += d
                    continue
                if roots[i] != i:
                    leaf_in_loop += d
                ph = phase_of(scopes.get((module, name)), phases)
                if ph is None:
                    unscoped_ops[name] += d
                else:
                    by[ph] += d
    coverage = leaf_in_loop / loop if loop > 0 else None
    ok = coverage is not None and coverage >= COVERAGE_MIN
    sec = 1e-9 / n
    rank = sorted(unscoped_ops.items(), key=lambda kv: -kv[1])[:top]
    return {"by_phase": {p: by[p] * sec for p in phases} if ok else None,
            "unscoped": (busy - sum(by.values())) * sec if ok else None,
            "coverage": coverage, "busy_s": busy * sec,
            "unscoped_ops": [[k, v * sec] for k, v in rank]}


def idle_by_span(devices, spans, window, prefix=PROGRAM_PREFIX) -> dict:
    """Idle seconds of the devices in the window (mean over them) by the
    innermost program span open on the window's thread, ``outside``
    where none is; each gap is cut at the spans' edges."""
    (lo, hi), thread = window
    mine = [(name[len(prefix):], s, e) for name, s, e, th in spans
            if th == thread and name.startswith(prefix)]
    n = max(1, len(devices))
    idle = defaultdict(float)
    for runs, _ in devices.values():
        cover = trace_reduce.union(runs, lo, hi)
        for gs, ge in trace_reduce.gaps(cover, lo, hi):
            cuts = sorted({gs, ge} | {t for _, s, e in mine for t in (s, e)
                                      if gs < t < ge})
            for a, b in zip(cuts, cuts[1:]):
                idle[innermost(mine, (a + b) / 2)] += (b - a) * 1e-9 / n
    return dict(idle)


def innermost(spans, t):
    """The span open at ``t`` that started last (of two that started
    together, the one that ends first), else ``outside``."""
    open_ = [(s, -e, name) for name, s, e in spans if s <= t < e]
    return max(open_)[2] if open_ else "outside"
