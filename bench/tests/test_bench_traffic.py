"""The traffic generator, against a fake server on the host."""
import threading
import time
from concurrent.futures import Future

import numpy as np

import bench_tiny  # noqa: F401  (paths)
from benchlib import traffic


class FakeTicket:
    def __init__(self, value, delay):
        self._f = Future()
        self.done_at = None

        def finish():
            time.sleep(delay)
            self.done_at = time.perf_counter()
            self._f.set_result(value)
        threading.Thread(target=finish, daemon=True).start()

    def result(self, timeout=None):
        return self._f.result(timeout)


def test_open_schedule_offers_the_same_work_for_every_seed():
    mix = {"loop": "open", "images": 4, "rate_per_s": 100,
           "burst_factor": 4, "burst_ms": 200, "burst_every_s": 2}
    a = traffic.open_schedule(mix, 10, np.random.default_rng(1))
    b = traffic.open_schedule(mix, 10, np.random.default_rng(2))
    # 5 periods of 0.2 s at 400/s and 1.8 s at 100/s
    assert len(a) == len(b) == 5 * (80 + 180)
    assert not np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 10
    in_bursts = np.sum((a % 2) < 0.2)
    assert in_bursts == 5 * 80


def test_closed_loop_keeps_each_client_to_one_request():
    inflight, high = [0], [0]
    lock = threading.Lock()

    def submit(i):
        with lock:
            inflight[0] += 1
            high[0] = max(high[0], inflight[0])
        t = FakeTicket(i, 0.005)

        def done(_):
            with lock:
                inflight[0] -= 1
        t._f.add_done_callback(done)
        return t

    mix = {"loop": "closed", "clients": 3, "images": 5}
    w = traffic.run(submit, mix, 0.3, seed=7)
    sent = w.sent()
    assert high[0] <= 3 and len(sent) > 20
    assert all(r.error is None and r.answer == r.image for r in sent)
    assert all(0 <= r.image < 5 for r in sent)
    assert w.completed_in_window() <= len(w.requests)
    again = traffic.run(submit, mix, 0.3, seed=7)
    first = [r.image for r in w.requests if r.client == 0][:5]
    assert first == [r.image for r in again.requests if r.client == 0][:5]


def test_open_loop_times_requests_from_when_they_were_due():
    mix = {"loop": "open", "images": 3, "rate_per_s": 200}

    def submit(i):
        time.sleep(0.006)  # 40 submits outlast the 0.2 s window: late
        return FakeTicket(i, 0.01)

    w = traffic.run(submit, mix, 0.2, seed=3)
    assert len(w.requests) == 40
    for r in w.requests:
        assert r.sent >= r.due
        assert r.latency >= r.done - r.sent
    assert max(r.sent - r.due for r in w.requests) > 0.01


def test_batch_sizes_cover_what_a_mix_can_form():
    assert list(traffic.batch_sizes({"loop": "closed", "clients": 1}, 8)) \
        == [1]
    assert list(traffic.batch_sizes({"loop": "closed", "clients": 16}, 8)) \
        == list(range(1, 9))
    assert list(traffic.batch_sizes({"loop": "open"}, 4)) == [1, 2, 3, 4]
