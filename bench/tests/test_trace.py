"""The reduction from a trace to numbers, on a small recorded trace."""
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.json")


@pytest.fixture
def small():
    with open(DATA) as f:
        doc = json.load(f)
    return trace.Trace([tuple(e) for e in doc["events"]], doc["window_s"])


def test_busy_is_the_union_of_operations_averaged_over_devices(small):
    # device 0: ops [0,40] and [30,60] and [100,120] us -> 80 us busy;
    # device 1: [0,50] and [50,90] -> 90 us.
    assert small.busy_s() == pytest.approx(85e-6)


def test_program_time_per_call(small):
    seconds, calls = small.module_time("rollout_window")
    assert calls == 1 and seconds == pytest.approx(60e-6)
    seconds, calls = small.module_time("window_loss")
    assert calls == 1 and seconds == pytest.approx(40e-6)


def test_breakdown_labels_idle_gaps_by_benchmark_span(small):
    b = small.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(40e-6)]
    assert b["idle_gaps"] == [["bench.bookkeeping", pytest.approx(40e-6)]]


def test_events_load_from_a_profiler_file(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracer = trace.Tracer(str(tmp_path))
    tracer.start()
    f(x).block_until_ready()
    tracer.stop(windows=1)
    t = tracer.load()
    assert any("bench.traced" in names for names, _, _ in t.lines.values())
    assert t.window_s > 0


def test_collective_time_counts_only_where_nothing_else_ran():
    ops = [("/device:TPU:0", "XLA Ops", "%all-reduce.1 = f32[8]", 0.0, 10e3),
           ("/device:TPU:0", "XLA Ops", "%fusion.2 = f32[8]", 5e3, 10e3),
           ("/device:TPU:1", "XLA Ops", "%all-reduce.1 = f32[8]", 0.0, 4e3)]
    t = trace.Trace(ops, 1.0)
    # device 0: all-reduce alone over [0, 5] us; device 1: over [0, 4] us.
    assert t.exposed_s("all-reduce") == pytest.approx(4.5e-6)
