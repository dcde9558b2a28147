"""Trace reduction: busy union, idle gaps, labels and top operations
(``bench/trace.py``), on hand-made intervals and on a trace recorded on the
CPU."""

import pytest

from bench import trace as tr


def test_union_merges_overlaps_and_clips():
    got = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (20, 30)], lo=1, hi=25)
    assert got == [(1, 3), (5, 9), (20, 25)]


def test_gaps_are_the_complement_inside_the_window():
    assert tr.gaps([(1, 3), (5, 9)], 0, 10) == [(0, 1), (3, 5), (9, 10)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_label_takes_the_innermost_host_span():
    host = [("bench.window", 0, 100), ("bench.step", 10, 50),
            ("bench.search.execute", 20, 40)]
    assert tr.label((25, 30), host) == "search.execute"
    assert tr.label((42, 48), host) == "step"
    assert tr.label((60, 70), host) == "idle"


def test_reduce_counts_busy_once_and_ranks_ops():
    trace = tr.Trace(
        device={"/device:TPU:0": [("a", 10, 30), ("b", 20, 40),
                                  ("a", 60, 70), ("c", 95, 120)]},
        host=[("bench.window", 0, 100), ("bench.step", 40, 60)])
    r = tr.reduce(trace)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(45e-9)          # 10-40, 60-70, 95-100
    assert [n for n, _ in r.top_ops] == ["a", "b", "c"]
    assert r.top_ops[0][1] == pytest.approx(30e-9)
    assert r.idle_gaps[0] == ("idle", pytest.approx(25e-9))
    assert r.idle_gaps[1] == ("step", pytest.approx(20e-9))
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(55e-9)


def test_reduce_averages_busy_over_chips():
    trace = tr.Trace(
        device={"/device:TPU:0": [("a", 0, 10)],
                "/device:TPU:1": [("a", 0, 30)]},
        host=[("bench.window", 0, 40)])
    assert tr.reduce(trace).busy_s == pytest.approx(20e-9)


def test_reduce_without_device_ops_or_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(device={}, host=[("bench.window", 0, 1)]))
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(device={"d": [("a", 0, 1)]}, host=[]))


def cpu_ops(plane, line):
    return plane == "/host:CPU" and line.startswith("tf_XLA")


def test_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.load(tr.find_xplane(str(tmp_path)), cpu_ops)
    assert {n for n, _, _ in trace.host} == {"bench.window", "bench.step"}
    r = tr.reduce(trace)
    assert 0 < r.busy_s <= r.window_s
    assert r.top_ops and all(s > 0 for _, s in r.top_ops)
    assert {n for n, _ in r.idle_gaps} <= {"step", "idle"}
