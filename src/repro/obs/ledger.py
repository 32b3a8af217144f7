"""Host-side run ledger: nested wall-clock spans, counters, warnings,
interval-series snapshots and a provenance stamp, exported as JSONL.

The jitted stack is a black box between ``runner(leaves, cld, es0)``
and the NumPy pull-back — this module makes the *host* half of a run
observable: where wall-clock went (compile vs dispatch vs summarize),
how the runner cache behaved (``driver.cache_stats()`` counters feed
``add_cache_stats``), and on which jax/device fleet the numbers were
measured (``provenance_stamp`` — the single shared helper behind the
benchmark artifact stamps in ``benchmarks/_provenance``).

The process-global default ledger (``get_ledger`` outside any scope)
records nothing: the driver and the streaming path instrument every
call, and with recording off each span, counter or warning costs one
flag test, so a long serve keeps flat memory.  ``use_ledger`` scopes a
recording ``RunLedger``: a lock plus a dict append per event.  Each span
keeps its start on the ``time.perf_counter`` clock beside its duration;
a ledger made with ``annotate=True`` also opens a
``jax.profiler.TraceAnnotation("repro.<span>")`` around each span, so a
profiler trace shows the program's spans on the device events' clock.
``tools/obs_report.py`` renders a dumped ledger into a text report
(span tree, cache stats, sparkline interval curves).
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager, nullcontext

#: what a span of a ledger that records nothing enters
_OFF = nullcontext()

#: prefix of the profiler annotations of an ``annotate=True`` ledger
ANNOTATION_PREFIX = "repro."


def provenance_stamp(**knobs) -> dict:
    """The run-provenance stamp: jax version + device fleet + dispatch
    knobs.  Pass knobs as keyword overrides; unpassed knobs record the
    process-wide defaults (env var / no device mesh)."""
    import jax
    prov = {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
        "cpu_count": os.cpu_count(),
        # the jitted simulator's dispatch knobs; None devices = the
        # host thread-chunk dispatcher (no device mesh)
        "substep_impl": os.environ.get("JAXSIM_SUBSTEP_IMPL", "xla"),
        "devices": None,
    }
    prov.update(knobs)
    return prov


class RunLedger:
    """Append-only trace of one run: spans (nested via a thread-local
    stack, or an explicit ``parent=`` id for worker threads), counters,
    warnings, named interval series, and an optional cache-stats
    snapshot.  ``dump`` writes one JSON object per line.

    ``record=False`` (the process default) keeps nothing;
    ``annotate=True`` also writes every span into a running profiler
    trace as ``repro.<name>``."""

    def __init__(self, name: str = "run", annotate: bool = False,
                 record: bool = True):
        self.name = name
        self.record = record
        self.created_s = time.time()
        self.provenance = None
        self.cache_stats = None
        self.events = []
        self.counters = {}
        self.series = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._next_id = 0
        self._annotation = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation

    # ------------------------------------------------------------ spans

    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_span(self):
        """Id of the innermost open span on THIS thread (None at root) —
        hand it to worker threads as their ``span(parent=...)``."""
        st = self._stack()
        return st[-1] if st else None

    def span(self, name: str, parent=None, **attrs):
        """Record a wall-clock span: its start (``perf_counter``) and
        duration.  Nesting comes from the per-thread span stack;
        ``parent`` overrides it (how thread-pool chunk spans attach under
        the dispatch span that forked them).  A ledger that records
        nothing returns a shared no-op context."""
        if not self.record:
            return _OFF
        return self._span(name, parent, attrs)

    @contextmanager
    def _span(self, name, parent, attrs):
        with self._lock:
            sid = self._next_id
            self._next_id += 1
        st = self._stack()
        pid = parent if parent is not None else (st[-1] if st else None)
        ann = self._annotation(ANNOTATION_PREFIX + name) \
            if self._annotation else _OFF
        st.append(sid)
        t0 = time.perf_counter()
        try:
            with ann:
                yield sid
        finally:
            dur = time.perf_counter() - t0
            st.pop()
            ev = {"kind": "span", "id": sid, "parent": pid, "name": name,
                  "start_s": t0, "dur_s": dur}
            if attrs:
                ev["attrs"] = attrs
            with self._lock:
                self.events.append(ev)

    def spans(self, name: str):
        """The recorded spans called ``name``, in the order they ended."""
        with self._lock:
            return [e for e in self.events
                    if e["kind"] == "span" and e["name"] == name]

    # ------------------------------------------- counters / warnings / data

    def count(self, name: str, n: int = 1):
        if not self.record:
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def warn(self, message: str, **attrs):
        if not self.record:
            return
        ev = {"kind": "warning", "message": message}
        if attrs:
            ev["attrs"] = attrs
        with self._lock:
            self.events.append(ev)

    def warnings(self):
        with self._lock:
            return [e for e in self.events if e["kind"] == "warning"]

    def add_series(self, name: str, cols, data):
        """Attach a named (T, C) interval series (e.g. one trace's
        ``summary["telemetry"]`` payload) for the report's curves."""
        if not self.record:
            return
        import numpy as np
        arr = np.asarray(data, np.float64)
        if arr.ndim != 2 or arr.shape[1] != len(tuple(cols)):
            raise ValueError(f"series {name!r}: data {arr.shape} does not "
                             f"match {len(tuple(cols))} cols")
        with self._lock:
            self.series.append({"name": name, "cols": list(cols),
                                "data": arr.tolist()})

    def add_cache_stats(self, stats: dict):
        """Snapshot ``driver.cache_stats()`` into the ledger (last call
        wins — take it after the runs you are reporting on)."""
        if not self.record:
            return
        with self._lock:
            self.cache_stats = dict(stats)

    def stamp(self, **knobs) -> dict:
        """Fill the provenance block (lazy: imports jax)."""
        self.provenance = provenance_stamp(**knobs)
        return self.provenance

    # ---------------------------------------------------------- profiling

    @contextmanager
    def profile(self, trace_dir: str):
        """Opt-in ``jax.profiler`` trace around a block; the TensorBoard
        trace lands under ``trace_dir`` and the block is also recorded
        as a ledger span."""
        import jax
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        try:
            with self.span("profile", trace_dir=trace_dir):
                yield
        finally:
            jax.profiler.stop_trace()

    # ------------------------------------------------------------- export

    def to_lines(self):
        with self._lock:
            lines = [{"kind": "meta", "name": self.name,
                      "created_s": self.created_s,
                      "provenance": self.provenance}]
            lines += list(self.events)
            lines.append({"kind": "counters",
                          "counters": dict(self.counters)})
            if self.cache_stats is not None:
                lines.append({"kind": "cache_stats", **self.cache_stats})
            lines += [{"kind": "series", **s} for s in self.series]
        return lines

    def dump(self, path: str) -> str:
        """Write the ledger as JSONL (one event per line)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            for ln in self.to_lines():
                f.write(json.dumps(ln) + "\n")
        return path


def load_ledger_lines(path: str):
    """Parse a dumped JSONL ledger back into its event dicts."""
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


_ACTIVE = RunLedger("default", record=False)


def get_ledger() -> RunLedger:
    """The currently-active ledger: the process-global default, which
    records nothing, unless a ``use_ledger`` scope is open."""
    return _ACTIVE


@contextmanager
def use_ledger(ledger: RunLedger):
    """Route driver/benchmark instrumentation into ``ledger`` for the
    scope's duration, then restore the previous one."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = ledger
    try:
        yield ledger
    finally:
        _ACTIVE = prev
