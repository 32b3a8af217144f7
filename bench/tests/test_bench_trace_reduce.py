"""The reduction from a profiler trace to device busy/idle time, top
device operations and idle gaps by harness span."""
import pytest

from bench import trace_reduce as tr


def test_union_and_gaps_clip_to_window():
    cover = tr.union([(5, 8), (0, 3), (2, 4), (9, 30)], 1, 20)
    assert cover == [[1, 4], [5, 8], [9, 20]]
    assert tr.gaps(cover, 0, 25) == [(0, 1), (4, 5), (8, 9), (20, 25)]


def test_reduce_by_hand():
    ms = 1_000_000
    devices = {"/device:TPU:0": ([(0, 5 * ms), (8 * ms, 9 * ms)],
                                 [("fusion.1", 0, 4 * ms),
                                  ("fusion.2", 4 * ms, 5 * ms),
                                  ("copy", 8 * ms, 9 * ms)]),
               "/device:TPU:1": ([(0, 10 * ms)], [("fusion.1", 0, 10 * ms)])}
    main, feeder = ("/host:CPU", 0), ("/host:CPU", 1)
    spans = [("window", 0, 10 * ms, main), ("chunk", 0, 6 * ms, main),
             ("feed", 5 * ms, 9 * ms, feeder), ("idle", 6 * ms, 9 * ms, main),
             ("wait", 9 * ms, 10 * ms, main)]
    r = tr.reduce(devices, spans, tr.window_of(spans))
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_by_device"]["/device:TPU:0"] == pytest.approx(0.006)
    assert r["busy_s"] == pytest.approx(0.008)
    ops = dict(map(tuple, r["breakdown"]["device_ops"]))
    assert ops["fusion.1"] == pytest.approx((0.004 + 0.010) / 2)
    idle = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    # device 0 idles 5-8 ms (at its middle the window's thread is in
    # "idle"; the feeder thread's span does not count) and 9-10 ms (wait)
    assert idle == {"idle": pytest.approx(0.0015),
                    "wait": pytest.approx(0.0005)}


def test_op_names_are_short():
    assert tr.op_name("%while.1494 = (u32[], f32[512]) while(...)") \
        == "while.1494"
    assert tr.op_name("jit_run_chunk(99)") == "jit_run_chunk(99)"


def test_cpu_recorded_trace(tmp_path):
    """A trace recorded here, with the CPU's XLA threads standing in for
    the device plane: the window annotation is found, the busy time is
    that of the program's operations and lies inside the window."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.chunk"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    xla = lambda ln: ln.startswith("tf_XLA")
    devices, spans = tr.load(tr.find_xplane(str(tmp_path)),
                             is_device=lambda p: p == "/host:CPU",
                             is_busy=xla, is_ops=xla)
    assert sum(n == "chunk" for n, _, _, _ in spans) == 3
    r = tr.reduce(devices, spans, tr.window_of(spans))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["breakdown"]["device_ops"]
