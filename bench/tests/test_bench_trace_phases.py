"""The phase pass and the idle-by-program-span pass over a profiler
trace (``bench.trace_phases``)."""
import time

import pytest

from bench import trace_phases as tp
from bench import trace_reduce as tr

MS = 1_000_000
MAIN, FEEDER = ("/host:CPU", 0), ("/host:CPU", 1)


def _op(name, s, e, line=0):
    return ("jit_run_chunk", name, s * MS, e * MS, line)


def _scopes(**by_op):
    return {("jit_run_chunk", k.replace("_", ".")): v
            for k, v in by_op.items()}


def _window(lo, hi):
    return [("window", lo * MS, hi * MS, MAIN)]


def test_phases_count_each_nanosecond_once():
    """Leaves inside the loop operation are counted, the loop and a
    parent fusion never beside them; busy time in no phase is
    unscoped."""
    body = "jit(run_chunk)/while/body/closed_call"
    ops = [_op("copy.1", 0, 1),                        # before the loop
           _op("while.9", 1, 9),                       # the loop
           _op("fusion.1", 1, 3),
           _op("fusion.2", 3, 6),
           _op("fusion.3", 3, 4),                      # nested in .2
           _op("fusion.4", 4, 6),
           _op("add.1", 6, 8),
           _op("fusion.5", 8, 9)]
    scopes = _scopes(fusion_1=body + "/decide/dot",
                     fusion_2=body + "/substeps/mul",
                     fusion_3=body + "/substeps/mul",
                     fusion_4=body + "/substeps/add",
                     add_1="jit(run_chunk)/while/body/add",
                     fusion_5=body + "/place/while/body/decide/x")
    devices = {"/device:TPU:0": ([(0, 10 * MS)], ops)}
    spans = _window(0, 10)
    r = tp.phases(devices, tr.window_of(spans), scopes)
    assert r["coverage"] == pytest.approx(1.0)
    ph = r["by_phase"]
    assert ph["decide"] == pytest.approx(0.002)
    assert ph["substeps"] == pytest.approx(0.003)      # not 0.006
    assert ph["place"] == pytest.approx(0.001)         # outermost wins
    assert ph["admit"] == ph["telemetry"] == 0.0
    # busy 10 ms - 6 ms in phases: the copy (1 ms), the loop's add
    # (2 ms) and 1 ms of busy time that no operation covers
    assert r["unscoped"] == pytest.approx(0.004)
    assert r["busy_s"] == pytest.approx(0.010)
    assert sum(ph.values()) + r["unscoped"] == pytest.approx(r["busy_s"])
    assert dict(map(tuple, r["unscoped_ops"])) == {
        "add.1": pytest.approx(0.002), "copy.1": pytest.approx(0.001)}


def test_phases_clip_to_window_and_average_devices():
    devices = {d: ([(0, 10 * MS)], [_op("while.1", 0, 10), _op("f", 0, 10)])
               for d in ("/device:TPU:0", "/device:TPU:1")}
    r = tp.phases(devices, tr.window_of(_window(2, 6)),
                  _scopes(f="x/while/body/decide/y"))
    assert r["by_phase"]["decide"] == pytest.approx(0.004)
    assert r["busy_s"] == pytest.approx(0.004)


def test_coverage_guard_returns_none():
    """Leaves covering under 90 % of the loop's time mean dropped
    events: the split is not read, whatever it would say."""
    s = "x/while/body/place/y"
    scopes = _scopes(f_1=s, f_2=s, f_3=s)
    ops = [_op("while.1", 0, 10), _op("f.1", 0, 4),
           _op("f.2", 5, 9)]                          # 8 of 10 ms
    devices = {"/device:TPU:0": ([(0, 10 * MS)], ops)}
    r = tp.phases(devices, tr.window_of(_window(0, 10)), scopes)
    assert r["coverage"] == pytest.approx(0.8)
    assert r["by_phase"] is None and r["unscoped"] is None
    ops.append(_op("f.3", 9, 10))
    ok = tp.phases(devices, tr.window_of(_window(0, 10)), scopes)
    assert ok["by_phase"]["place"] == pytest.approx(0.009)


def test_phase_of_and_op_scopes():
    assert tp.phase_of("jit(f)/while/body/closed_call/apply/scatter") \
        == "apply"
    assert tp.phase_of("jit(f)/while/cond/lt") is None
    assert tp.phase_of(None) is None
    text = ("HloModule jit_run_chunk, entry_computation_layout={...}\n"
            '  %fusion.7 = f64[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(run_chunk)/while/body/admit/add" '
            'source_file="x.py"}\n'
            '  ROOT %tuple.1 = (f64[8]{0}) tuple(%fusion.7)\n')
    assert tp.op_scopes(text) == {
        ("jit_run_chunk", "fusion.7"): "jit(run_chunk)/while/body/admit/add"}
    assert tp.module_name("jit_run_chunk(42)") == "jit_run_chunk"


def test_module_of_an_event_is_the_execution_it_runs_in():
    """A TPU's operation events name no module: the execution that
    holds the event's start gives it."""
    runs = [(0, 5, "jit_convert"), (10, 20, "jit_run_chunk"),
            (30, 40, "jit_run_chunk")]
    assert [tp.module_at(runs, t) for t in (0, 4, 7, 10, 19, 20, 35, 50)] \
        == ["jit_convert", "jit_convert", "", "jit_run_chunk",
            "jit_run_chunk", "", "jit_run_chunk", ""]


def test_idle_gaps_by_program_span():
    """Each idle gap is cut at the program spans' edges and each piece
    goes to the innermost span open on the window's thread; the
    harness's spans and the feeder thread's do not label."""
    devices = {"/device:TPU:0": ([(2 * MS, 5 * MS), (9 * MS, 10 * MS)],
                                 [])}
    spans = _window(0, 12) + [
        ("chunk", 0, 12 * MS, MAIN),                  # bench span
        ("repro.stream_put", 0, 1 * MS, MAIN),
        ("repro.stream_dispatch", 1 * MS, 3 * MS, MAIN),
        ("repro.stream_sync", 3 * MS, 6 * MS, MAIN),
        ("repro.stream_fetch", 6 * MS, 7 * MS, MAIN),
        ("repro.inner", 6 * MS, 6.5 * MS, MAIN),
        ("repro.stream_put", 7 * MS, 12 * MS, FEEDER)]
    idle = tp.idle_by_span(devices, spans, tr.window_of(spans))
    assert idle == {"stream_put": pytest.approx(0.001),
                    "stream_dispatch": pytest.approx(0.001),
                    "stream_sync": pytest.approx(0.001),
                    "inner": pytest.approx(0.0005),
                    "stream_fetch": pytest.approx(0.0005),
                    "outside": pytest.approx(0.004)}
    total_idle = 12 * 1e-3 - 4 * 1e-3
    assert sum(idle.values()) == pytest.approx(total_idle)


def test_cpu_recorded_trace_of_a_scoped_loop(tmp_path):
    """A trace recorded here of a jitted ``fori_loop`` with two phase
    scopes, the CPU's XLA threads standing in for the device: both
    phases get time from the HLO text's scopes, the leaves cover the
    loop, and the idle time of a host step lands in its program span."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from jax import lax

    from repro.obs import RunLedger, use_ledger

    def body(i, x):
        with jax.named_scope("decide"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("substeps"):
            return jnp.cos(y) * 0.5 + x * 0.5

    f = jax.jit(lambda x: lax.fori_loop(0, 40, body, x))
    x = jnp.full((128, 128), 0.01)
    f(x).block_until_ready()
    scopes = tp.op_scopes(f.lower(x).compile().as_text())
    led = RunLedger("cpu", annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"), use_ledger(led):
        for _ in range(3):
            with led.span("stream_put"):
                time.sleep(0.02)
            with led.span("stream_dispatch"):
                y = f(x)
            with led.span("stream_sync"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    xla = lambda ln: ln.startswith("tf_XLA")
    devices, spans = tp.load(tr.find_xplane(str(tmp_path)),
                             is_device=lambda p: p == "/host:CPU",
                             is_busy=xla, is_ops=xla)
    assert sum(n == "repro.stream_put" for n, _, _, _ in spans) == 3
    window = tr.window_of(spans)
    r = tp.phases(devices, window, scopes)
    assert r["coverage"] >= tp.COVERAGE_MIN
    assert r["by_phase"]["decide"] > 0 and r["by_phase"]["substeps"] > 0
    assert 0 <= r["unscoped"] < r["busy_s"]
    idle = tp.idle_by_span(devices, spans, window)
    assert idle["stream_put"] >= 0.05                 # three 20 ms sleeps
    assert idle["stream_put"] > 2 * idle.get("stream_dispatch", 0.0)
