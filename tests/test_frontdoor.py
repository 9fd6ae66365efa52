"""The continuous-batching front door: mid-flight admission, the
cross-network device scheduler, the options-object API (+ deprecation
shim), and the unified ``Ticket`` handle.

Batcher-level tests drive ``MicroBatcher`` with stub engines (dispatch
timing is the subject, not convolution), so they are fast and
deterministic; API tests use real tiny networks where numerics matter.
"""
import contextlib
import threading
import time
import warnings
from collections import Counter

import numpy as np
import pytest

from repro.serving import (
    DeviceScheduler,
    MicroBatcher,
    Overloaded,
    RequestOptions,
    Server,
    ServingOptions,
    Ticket,
    spans,
)
from repro.serving.batcher import LONE_STREAK


class FakeEngine:
    """Engine stub: echoes per-image sums so results are checkable, and
    sleeps a configurable service time so tests control dispatch
    duration."""

    def __init__(self, service_s=0.0):
        self.service_s = service_s
        self.batches = []  # batch size per dispatch, in dispatch order

    def run(self, image):
        self.batches.append(1)
        if self.service_s:
            time.sleep(self.service_s)
        return np.asarray(image).sum(keepdims=True)

    def run_batch(self, images):
        images = np.asarray(images)
        self.batches.append(images.shape[0])
        if self.service_s:
            time.sleep(self.service_s)
        return images.sum(axis=(1, 2, 3), keepdims=True)


def _img(v):
    return np.full((4, 4, 3), float(v), dtype=np.float32)


# ---------------------------------------------------------------------------
# mid-flight admission (the continuous-batching core)


def test_requests_join_forming_batch_during_dispatch():
    """Requests arriving while the loop is busy dispatching coalesce into
    ONE next batch instead of one window each — the mid-flight admission
    the deadline-window design couldn't do."""
    engine = FakeEngine(service_s=0.15)
    with MicroBatcher(engine, max_batch=8, window_ms=0.0) as b:
        t0 = b.submit(_img(0))  # dispatches alone (window 0)
        time.sleep(0.05)        # loop is now inside the 0.15s dispatch
        rest = [b.submit(_img(i + 1)) for i in range(3)]
        t0.result(timeout=10)
        for t in rest:
            t.result(timeout=10)
    assert engine.batches == [1, 4]  # 3 coalesced, padded to the 4-bucket
    assert [d["batch"] for d in b.dispatches] == [1, 3]
    assert b.stats()["joined_forming"] == 2  # 2 of the 3 joined a form
    # numerics unchanged by coalescing: each result is its own image sum
    assert rest[1].result()[0] == pytest.approx(4 * 4 * 3 * 2.0)


def test_window_anchored_at_oldest_arrival():
    """The batching window is measured from the OLDEST pending request's
    arrival, not from when the loop dequeues: a late joiner rides out the
    remainder of the first request's window instead of restarting it."""
    engine = FakeEngine()
    with MicroBatcher(engine, max_batch=8, window_ms=200.0) as b:
        t0 = time.perf_counter()
        first = b.submit(_img(1))
        time.sleep(0.12)  # join mid-window
        late = b.submit(_img(2))
        first.result(timeout=10)
        late.result(timeout=10)
        wall = time.perf_counter() - t0
    # one shared dispatch at ~t0+0.2; a window restarted at the late
    # join (or at dequeue) would push wall past ~0.32
    assert engine.batches == [2]
    assert wall < 0.30, f"window restarted: wall {wall:.3f}s"
    assert b.stats()["dispatch_causes"]["window"] == 1
    assert b.stats()["joined_forming"] == 1


def test_mid_flight_batch_respects_max_batch():
    """The forming batch never exceeds max_batch: overflow requests roll
    into the following dispatch."""
    engine = FakeEngine(service_s=0.15)
    with MicroBatcher(engine, max_batch=2, window_ms=0.0) as b:
        first = b.submit(_img(0))
        time.sleep(0.05)
        rest = [b.submit(_img(i)) for i in range(3)]
        for t in [first, *rest]:
            t.result(timeout=10)
    assert engine.batches == [1, 2, 1]


def test_bitwise_equal_to_sequential_with_mid_flight_admission():
    """The acceptance contract survives the rework: coalesced results are
    bitwise-equal to sequential engine.run, even when requests joined the
    batch mid-flight."""
    import jax

    from repro.configs import get, tiny_variant
    from repro.core import InferenceEngine

    engine = InferenceEngine(tiny_variant(get("resnet18")))
    key = jax.random.key(7)
    imgs = [jax.random.normal(jax.random.fold_in(key, i), (32, 32, 3))
            for i in range(5)]
    seq = [np.asarray(engine.run(im)) for im in imgs]
    with MicroBatcher(engine, max_batch=4, window_ms=40.0) as b:
        tickets = []
        for im in imgs:  # trickle in so later ones join mid-flight
            tickets.append(b.submit(im))
            time.sleep(0.005)
        got = [np.asarray(t.result(timeout=120)) for t in tickets]
    for g, s in zip(got, seq):
        np.testing.assert_array_equal(g, s)
    assert b.stats()["joined_forming"] >= 1  # coalescing actually happened


# ---------------------------------------------------------------------------
# a lone request skips the window


@pytest.mark.parametrize("scheduled", [False, True],
                         ids=["inline", "scheduled"])
def test_lone_closed_loop_caller_skips_the_window_after_the_streak(
        scheduled):
    """One caller that waits on each answer never sends a partner: from
    the LONE_STREAK-th lone arrival on, each request dispatches at once
    instead of waiting out the 200 ms window."""
    engine = FakeEngine()
    n = 20
    with contextlib.ExitStack() as stack:
        sched = (stack.enter_context(DeviceScheduler()) if scheduled
                 else None)
        b = stack.enter_context(MicroBatcher(
            engine, max_batch=8, window_ms=200.0, scheduler=sched))
        tickets = []
        for i in range(n):
            t = b.submit(_img(i))
            t.result(timeout=10)
            tickets.append(t)
    assert engine.batches == [1] * n
    assert b.stats()["dispatch_causes"] == {
        "full": 0, "window": LONE_STREAK - 1,
        "lone": n - LONE_STREAK + 1, "drain": 0}
    for t in tickets[:LONE_STREAK - 1]:
        assert t.latency >= 0.19, "a request before the streak skipped"
    for t in tickets[LONE_STREAK - 1:]:
        assert t.latency < 0.1, f"lone request waited {t.latency:.3f}s"


def test_resubmitting_from_the_answer_still_counts_as_lone():
    """A caller that sends its next request from the callback handing it
    the answer (on the batcher's thread, while the dispatch resolves)
    finds nothing in flight: the in-flight flag clears before the first
    request resolves, so such a chain reaches the streak too."""
    engine = FakeEngine()
    n = LONE_STREAK + 4
    tickets = []
    done = threading.Event()
    with MicroBatcher(engine, max_batch=8, window_ms=20.0) as b:
        def send():
            t = b.submit(_img(len(tickets)))
            tickets.append(t)
            t.add_done_callback(
                lambda _t: send() if len(tickets) < n else done.set())

        send()
        assert done.wait(10)
    assert len(tickets) == n
    assert b.stats()["dispatch_causes"] == {
        "full": 0, "window": LONE_STREAK - 1,
        "lone": n - LONE_STREAK + 1, "drain": 0}


@pytest.mark.parametrize("joiners", [1, 2])
def test_a_burst_after_the_streak_resets_it_and_coalesces_again(joiners):
    """After the streak, a burst's first request goes out alone; the ones
    that arrive during its dispatch reset the streak, even one with
    nothing pending beside it, and share the next dispatch; the burst
    after that coalesces under the window."""
    engine = FakeEngine()
    with MicroBatcher(engine, max_batch=8, window_ms=50.0) as b:
        for i in range(LONE_STREAK):
            b.submit(_img(i)).result(timeout=10)
        engine.service_s = 0.1
        first = b.submit(_img(0))  # lone: dispatches at once
        deadline = time.perf_counter() + 5
        while len(engine.batches) <= LONE_STREAK \
                and time.perf_counter() < deadline:
            time.sleep(0.001)
        rest = [b.submit(_img(i + 1)) for i in range(joiners)]
        for t in [first, *rest]:
            t.result(timeout=10)
        burst = [b.submit(_img(i)) for i in range(3)]
        for t in burst:
            t.result(timeout=10)
    assert [d["batch"] for d in b.dispatches][LONE_STREAK:] \
        == [1, joiners, 3]
    assert b.stats()["dispatch_causes"] == {
        "full": 0, "window": LONE_STREAK - 1 + 2, "lone": 2, "drain": 0}


def test_a_busy_device_keeps_the_window():
    """While another network's jobs hold the shared device, a lone
    request would queue anyway: it waits out the window as before, so no
    dispatch is lone. Once the device is free, the next one is."""
    engine = FakeEngine()
    stop = threading.Event()
    with DeviceScheduler() as sched:
        def feed():  # two feeders: one job running, the next queued
            while not stop.is_set():
                sched.run(lambda: time.sleep(0.03),
                          urgency=time.perf_counter(), network="slow")

        feeders = [threading.Thread(target=feed, daemon=True)
                   for _ in range(2)]
        for t in feeders:
            t.start()
        deadline = time.perf_counter() + 5
        while sched.stats()["jobs"] < 2 and time.perf_counter() < deadline:
            time.sleep(0.001)
        with MicroBatcher(engine, max_batch=8, window_ms=20.0,
                          scheduler=sched, name="fast") as b:
            try:
                for i in range(LONE_STREAK + 4):
                    t = b.submit(_img(i))
                    t.result(timeout=10)
                    assert t.latency >= 0.019, "skipped the window"
                while_busy = dict(b.stats()["dispatch_causes"])
            finally:
                stop.set()
                for t in feeders:
                    t.join(10)
            assert not sched.busy()
            b.submit(_img(0)).result(timeout=10)
    assert while_busy == {"full": 0, "window": LONE_STREAK + 4,
                          "lone": 0, "drain": 0}
    assert b.stats()["dispatch_causes"]["lone"] == 1


def test_batcher_window_span_carries_its_cause():
    """The ``batcher.window`` span says why its batch was taken, so a
    reader can tell the lone path from the window."""
    assert spans.active is None
    rec = spans.start()
    try:
        with MicroBatcher(FakeEngine(), max_batch=8, window_ms=10.0) as b:
            for i in range(LONE_STREAK + 2):
                b.submit(_img(i)).result(timeout=10)
    finally:
        got = rec.stop()
    causes = [s.attrs["cause"] for s in got if s.name == "batcher.window"]
    assert causes == ["window"] * (LONE_STREAK - 1) + ["lone"] * 3
    assert Counter(causes) == Counter(b.stats()["dispatch_causes"])


def test_lone_bookkeeping_under_contention():
    """Closed-loop callers, more than cores, with a short switch interval:
    every request gets its own answer, and the streak and in-flight flag
    leave the counts whole (each dispatch has one cause, the batcher ends
    idle)."""
    import sys

    engine = FakeEngine()
    callers, each = 16, 25
    wrong = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with DeviceScheduler() as sched, MicroBatcher(
                engine, max_batch=4, window_ms=1.0, scheduler=sched) as b:
            def caller(c):
                for i in range(each):
                    v = c * each + i
                    out = b.submit(_img(v)).result(timeout=30)
                    if out[0] != 4 * 4 * 3 * float(v):
                        wrong.append(v)

            threads = [threading.Thread(target=caller, args=(c,))
                       for c in range(callers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    st = b.stats()
    assert wrong == []
    assert st["requests"] == callers * each
    assert sum(st["dispatch_causes"].values()) == st["dispatches"]
    assert st["queue_depth"] == 0 and not b._in_flight


def test_scheduler_busy_while_a_job_runs_or_waits():
    with DeviceScheduler() as sched:
        assert not sched.busy()
        gate, release = threading.Event(), threading.Event()

        def hold():
            gate.set()
            release.wait(5)

        holder = threading.Thread(
            target=lambda: sched.run(hold, urgency=0.0))
        holder.start()
        assert gate.wait(5)
        assert sched.busy() and sched.stats()["queued"] == 0  # running
        waiter = threading.Thread(
            target=lambda: sched.run(lambda: None, urgency=1.0))
        waiter.start()
        deadline = time.perf_counter() + 5
        while sched.stats()["queued"] < 1 \
                and time.perf_counter() < deadline:
            time.sleep(0.001)
        assert sched.busy()
        release.set()
        holder.join(5)
        waiter.join(5)
        assert not sched.busy()


# ---------------------------------------------------------------------------
# device scheduler


def test_scheduler_runs_jobs_and_relays_errors():
    with DeviceScheduler() as sched:
        assert sched.run(lambda: 42, urgency=0.0) == 42
        with pytest.raises(ValueError, match="boom"):
            sched.run(lambda: (_ for _ in ()).throw(ValueError("boom")),
                      urgency=0.0)
    with pytest.raises(RuntimeError, match="closed"):
        sched.run(lambda: 1, urgency=0.0)


def test_scheduler_orders_by_urgency_then_priority():
    """Queued jobs leave the heap oldest-deadline-first; priority sorts
    above the time key."""
    sched = DeviceScheduler()
    order = []
    gate = threading.Event()
    release = threading.Event()

    def job(tag, wait=False):
        def fn():
            if wait:
                gate.set()
                release.wait(5)
            order.append(tag)
        return fn

    threads = [threading.Thread(
        target=lambda: sched.run(job("hold", wait=True), urgency=0.0))]
    threads[0].start()
    assert gate.wait(5)  # device thread is pinned inside "hold"
    # enqueue out of urgency order while the device is busy
    for tag, urg, pri in (("late", 3.0, 0), ("soon", 1.0, 0),
                          ("mid", 2.0, 0), ("vip", 9.0, 1)):
        t = threading.Thread(
            target=lambda tag=tag, urg=urg, pri=pri: sched.run(
                job(tag), urgency=urg, priority=pri, network=tag))
        t.start()
        threads.append(t)
    deadline = time.perf_counter() + 5
    while sched.stats()["queued"] < 4 and time.perf_counter() < deadline:
        time.sleep(0.005)
    release.set()
    for t in threads:
        t.join(5)
    assert order == ["hold", "vip", "soon", "mid", "late"]
    assert sched.stats()["completed"]["vip"] == 1


def test_scheduler_fairness_fast_network_p95_bounded():
    """Slow + fast network sharing one device: each batcher has at most
    one dispatch in flight, so however deep the slow network's queue
    grows, a fast request waits behind at most one slow dispatch. Fast
    p95 stays under (1 slow + a few fast) service times — never the sum
    of the slow queue."""
    slow_engine = FakeEngine(service_s=0.08)
    fast_engine = FakeEngine(service_s=0.002)
    with DeviceScheduler() as sched:
        with MicroBatcher(slow_engine, max_batch=1, window_ms=0.0,
                          scheduler=sched, name="slow") as slow, \
                MicroBatcher(fast_engine, max_batch=1, window_ms=0.0,
                             scheduler=sched, name="fast") as fast:
            slow_tickets = [slow.submit(_img(i)) for i in range(8)]
            fast_lat = []
            for i in range(10):
                t = fast.submit(_img(i))
                t.result(timeout=30)
                fast_lat.append(t.latency)
            for t in slow_tickets:
                t.result(timeout=30)
    fast_lat.sort()
    p95 = fast_lat[min(len(fast_lat) - 1,
                       round(0.95 * (len(fast_lat) - 1)))]
    # bound: one in-flight slow dispatch (0.08s) + own service + slack.
    # Without per-network in-flight limiting, 8 queued slow dispatches
    # ahead would push this to ~0.64s.
    assert p95 < 0.25, f"fast p95 {p95:.3f}s head-of-line blocked"
    assert sched.stats()["jobs"] >= 18


# ---------------------------------------------------------------------------
# options objects + deprecation shim


def test_legacy_kwargs_warn_and_build_identical_server():
    new = Server(tiny=True, options=ServingOptions(
        max_batch=4, window_ms=3.0, deadline_ms=50.0, max_queue=7,
        breaker_threshold=2, breaker_reset_s=1.5))
    with pytest.warns(DeprecationWarning, match="ServingOptions"):
        old = Server(tiny=True, max_batch=4, window_ms=3.0,
                     deadline_ms=50.0, max_queue=7, breaker_threshold=2,
                     breaker_reset_s=1.5)
    try:
        assert old.options == new.options  # frozen dataclass: full equality
    finally:
        old.close()
        new.close()


def test_legacy_kwargs_conflict_with_options_raises():
    with pytest.raises(ValueError, match="not both"):
        Server(tiny=True, options=ServingOptions(), max_queue=3)


def test_unknown_server_kwarg_is_a_typeerror():
    with pytest.raises(TypeError, match="max_qeue"):
        Server(tiny=True, max_qeue=3)


def test_per_call_dtype_kwarg_warns_and_matches_options(tiny_server):
    import jax

    img = jax.random.normal(jax.random.key(3), (32, 32, 3))
    via_options = tiny_server.run(
        "resnet18", img, options=RequestOptions(dtype="bfloat16"))
    with pytest.warns(DeprecationWarning, match="RequestOptions"):
        via_kwarg = tiny_server.run("resnet18", img, dtype="bfloat16")
    np.testing.assert_array_equal(np.asarray(via_options),
                                  np.asarray(via_kwarg))


def test_conflicting_dtypes_raise():
    opts = RequestOptions(dtype="bfloat16")
    with pytest.raises(ValueError, match="conflicting"):
        opts.merged_dtype("float16")
    assert opts.merged_dtype("bfloat16") is opts
    assert opts.merged_dtype(None) is opts


@pytest.fixture(scope="module")
def tiny_server():
    with Server(tiny=True, options=ServingOptions(
            max_batch=4, window_ms=2.0)) as server:
        yield server


# ---------------------------------------------------------------------------
# Ticket


def test_submit_returns_ticket_with_latency_stamps(tiny_server):
    import jax

    img = jax.random.normal(jax.random.key(4), (32, 32, 3))
    ticket = tiny_server.submit("resnet18", img)
    assert isinstance(ticket, Ticket)
    out = ticket.result(timeout=120)
    assert ticket.done()
    assert out.ndim == 1  # (classes,) logits
    assert ticket.latency is not None and ticket.latency > 0
    assert ticket.done_at is not None and ticket.done_at > ticket.arrival
    # run() is submit().result() — same numerics, same handle semantics
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(tiny_server.run("resnet18", img)))


def test_ticket_result_timeout_cancels():
    """The cancel-on-timeout contract moved from Server.run onto
    Ticket.result: a timed-out wait marks the request so the batcher
    sheds it at dequeue."""
    engine = FakeEngine(service_s=0.2)
    with MicroBatcher(engine, max_batch=1, window_ms=0.0) as b:
        hold = b.submit(_img(0))        # occupies the loop 0.2s
        queued = b.submit(_img(1))      # waits behind it
        with pytest.raises(Exception) as ei:
            queued.result(timeout=0.01)
        assert "Timeout" in type(ei.value).__name__
        hold.result(timeout=10)
    assert b.stats()["shed"]["cancelled"] == 1
    assert engine.batches == [1]  # the cancelled request never dispatched


def test_ticket_done_callback_fires():
    engine = FakeEngine()
    seen = []
    with MicroBatcher(engine, max_batch=1, window_ms=0.0) as b:
        t = b.submit(_img(2))
        t.add_done_callback(lambda ticket: seen.append(ticket.id))
        t.result(timeout=10)
    assert seen == [t.id]


# ---------------------------------------------------------------------------
# public surface: examples/docs must not import serving internals


def test_examples_and_docs_use_public_import_surface():
    """Anything under examples/ or docs/ that imports the serving
    subsystem must go through ``repro.serving`` — the internals
    (``repro.serving.request``, ``.resilience``, ...) are free to move."""
    import re
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    private = re.compile(
        r"(?:from|import)\s+repro\.serving\.(\w+)")
    offenders = []
    for path in [*(root / "examples").rglob("*.py"),
                 *(root / "docs").rglob("*.md"),
                 root / "README.md"]:
        for i, line in enumerate(path.read_text().splitlines(), 1):
            m = private.search(line)
            if m:
                offenders.append(f"{path.relative_to(root)}:{i} "
                                 f"imports repro.serving.{m.group(1)}")
    assert not offenders, "\n".join(offenders)


def test_public_surface_exports_the_front_door():
    import repro.serving as serving

    for name in ("Server", "ServingOptions", "RequestOptions", "Ticket",
                 "AsyncClient", "ServerEndpoint", "DeviceScheduler",
                 "Rejected", "Overloaded", "DeadlineExceeded",
                 "CircuitOpen", "ProtocolError", "BadRequest",
                 "RemoteError"):
        assert hasattr(serving, name), f"repro.serving.{name} missing"
        assert name in serving.__all__


# ---------------------------------------------------------------------------
# admission + close semantics survive the rework


def test_bounded_queue_sheds_at_admission_mid_flight():
    engine = FakeEngine(service_s=0.2)
    with MicroBatcher(engine, max_batch=1, window_ms=0.0,
                      max_queue=2) as b:
        first = b.submit(_img(0))
        time.sleep(0.05)  # first is mid-dispatch; queue empty again
        ok = [b.submit(_img(1)), b.submit(_img(2))]
        with pytest.raises(Overloaded, match="queue full"):
            b.submit(_img(3))
        for t in [first, *ok]:
            t.result(timeout=10)
    assert b.stats()["shed"]["overload"] == 1


def test_priority_and_deadline_ride_to_the_request():
    engine = FakeEngine()
    with MicroBatcher(engine, max_batch=1, window_ms=0.0) as b:
        req = b.submit_request(_img(0), deadline_ms=5000.0, priority=3)
        assert req.priority == 3
        assert req.deadline is not None
        assert req.urgency == req.deadline
        Ticket(req).result(timeout=10)
        no_dl = b.submit_request(_img(1))
        assert no_dl.deadline is None
        assert no_dl.urgency == no_dl.arrival
        Ticket(no_dl).result(timeout=10)
