"""The reduction from a trace to the per-layer numbers, on hand-made
events."""
import pytest

import bench_tiny  # noqa: F401
import trace_reduce as tr
from trace_reduce import Event, Trace


def test_union_counts_overlapping_ops_once():
    ops = [Event("a", 0, 10), Event("b", 5, 15), Event("c", 20, 30)]
    assert tr.merge([(0, 10), (5, 15), (20, 30)]) == [(0, 15), (20, 30)]
    assert tr.busy_ns(ops, 0, 40) == 25
    assert tr.busy_ns(ops, 8, 25) == 12            # clipped to the window
    assert tr.idle_gaps(ops, 0, 40) == [(15, 20), (30, 40)]


def test_summary_of_hand_made_trace():
    spans = [Event("bench.step.admit", 0, 100),
             Event("bench.client.submit", 100, 110),
             Event("bench.step.decode", 110, 200),
             Event("bench.step.decode", 200, 300)]
    host = [Event("PjitFunction(dot)", 20, 60), Event("dispatch", 150, 190)]
    ops = [Event("fusion", 10, 30), Event("dot", 40, 50),
           Event("dot", 120, 140), Event("copy", 250, 260),
           Event("late", 400, 500)]                  # outside the window
    s = tr.summarize(Trace({"/device:TPU:0": ops}, spans, host))
    assert s.window_ns == 300
    assert s.busy_ns == 20 + 10 + 20 + 10
    assert s.ops == 4
    assert s.span_count == {"bench.step.admit": 1, "bench.step.decode": 2}
    assert s.span_ops == {"bench.step.admit": 2, "bench.step.decode": 2}
    assert s.span_ns["bench.step.decode"] == 190
    assert s.top_ops[0] == ("dot", pytest.approx(30e-9))
    assert s.top_gaps[0] == ("bench.step.decode", pytest.approx(110e-9))
    assert s.top_gaps[1] == ("bench.step.admit", pytest.approx(70e-9))
    host.append(Event("dispatch", 190, 240))         # now covers 195
    s = tr.summarize(Trace({"/device:TPU:0": ops}, spans, host))
    assert s.top_gaps[0][0] == "bench.step.decode / dispatch"


def test_no_steps_or_no_device_gives_nothing():
    assert tr.summarize(Trace({}, [Event("bench.step.admit", 0, 1)])) is None
    assert tr.summarize(Trace({"/device:TPU:0": []}, [])) is None


def test_spans_are_read_from_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: jnp.tanh(a @ a))
    x = jnp.ones((64, 64), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.step.decode"):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    t = tr.read_xplane(tr.find_xplane(str(tmp_path)))
    assert [s.name for s in t.spans] == ["bench.step.decode"]
    assert t.host and t.device_ops == {}     # the CPU has no device plane
    assert tr.summarize(t) is None
