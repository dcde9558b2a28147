"""The traffic generator and its loops (``bench/loadgen.py``): every seed
gets the same work in another order, and the open loop times each request
from when it was due, on a fake service whose step sleeps a fixed time."""

import time

import numpy as np
import pytest

from bench import loadgen


def test_open_schedule_is_the_same_work_in_another_order():
    a_due, a_t = loadgen.open_schedule(100.0, 10.0, [3, 1], seed=1)
    b_due, b_t = loadgen.open_schedule(100.0, 10.0, [3, 1], seed=2**31 + 5)
    assert len(a_due) == len(b_due) == 1000
    gaps_a = np.sort(np.diff(np.concatenate([[0], a_due])))
    gaps_b = np.sort(np.diff(np.concatenate([[0], b_due])))
    assert np.allclose(gaps_a, gaps_b)
    assert not np.array_equal(a_t, b_t)
    assert np.bincount(a_t).tolist() == np.bincount(b_t).tolist() == \
        [750, 250]
    assert 0 < a_due[0] and a_due[-1] < 10.0
    assert np.all(np.diff(a_due) >= 0)


def test_open_schedule_repeats_for_a_seed():
    a = loadgen.open_schedule(50.0, 4.0, [1, 1, 1], seed=7)
    b = loadgen.open_schedule(50.0, 4.0, [1, 1, 1], seed=7)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_template_counts_are_exact_shares():
    assert loadgen.template_counts([32, 24, 24, 16, 24, 16], 136) == \
        [32, 24, 24, 16, 24, 16]
    assert sum(loadgen.template_counts([1, 1, 1], 10)) == 10


def test_closed_templates_hold_the_weights_in_every_block():
    it = loadgen.closed_templates([1, 3], seed=5)
    block = [next(it) for _ in range(4)]
    assert sorted(block) == [0, 1, 1, 1]


class SleepyServer:
    """Answers every queued request in one step that sleeps ``step_s``."""

    def __init__(self, step_s):
        self.step_s, self.queue, self.steps = step_s, [], 0

    def submit(self, request):
        self.queue.append(request)
        return request

    def pending(self):
        return len(self.queue)

    def step(self):
        time.sleep(self.step_s)
        done, self.queue = self.queue, []
        self.steps += 1
        return [(r, r * 2) for r in done]


def _loop(server):
    from contextlib import nullcontext
    return loadgen.Loop(server, lambda i, t: i, lambda name: nullcontext())


def test_open_loop_times_requests_from_when_they_were_due():
    server = SleepyServer(0.05)
    loop = _loop(server)
    due = np.asarray([0.0, 0.01, 0.02, 0.2])
    loop.open_loop(due, np.zeros(4, int), seconds=0.3, drain_s=1.0)
    recs = sorted(loop.records, key=lambda r: r.index)
    assert [r.result for r in recs] == [0, 2, 4, 6]
    # the first step starts at once and holds the next two requests back:
    # their latency counts the wait from their due time, not their submit
    assert recs[0].latency == pytest.approx(0.05, abs=0.02)
    assert recs[1].submitted - recs[1].due >= 0.03
    assert recs[1].latency >= 0.08 and recs[2].latency >= 0.07
    assert recs[3].latency == pytest.approx(0.05, abs=0.02)
    assert max(loop.lateness()) >= 0.03
    assert server.steps == 3


def test_open_loop_drains_at_most_drain_s_past_the_close():
    class Stuck(SleepyServer):
        def step(self):
            time.sleep(self.step_s)
            return []
    loop = _loop(Stuck(0.01))
    t0 = time.perf_counter()
    loop.open_loop(np.asarray([0.0]), np.zeros(1, int), seconds=0.05,
                   drain_s=0.1)
    assert time.perf_counter() - t0 < 0.5
    assert not any(r.answered for r in loop.records)


def test_closed_loop_keeps_each_client_busy_until_the_close():
    server = SleepyServer(0.02)
    loop = _loop(server)
    loop.closed_loop(3, iter(lambda: 0, None), seconds=0.2, drain_s=1.0)
    assert len(loop.records) >= 3 * 8
    answered = [r for r in loop.records if r.answered]
    assert len(answered) == len(loop.records)
    # a client's next request is due when its last one was answered
    assert all(0 <= r.latency < 0.1 for r in answered)
