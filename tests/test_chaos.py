"""Chaos suite: the serving tier under scripted faults.

Every test drives a deterministic ``FaultInjector`` script through the
real serving stack (no monkeypatched internals) and asserts the issue's
acceptance bar:

  (a) transient dispatch faults are retried — every accepted Future
      resolves, bitwise-equal to the unfaulted engine;
  (b) persistent faults trip the circuit breaker and degrade the engine
      to the xla-only fallback plan — serving continues, and the
      degraded counter surfaces in ``Server.stats()``;
  (c) overload sheds with *typed* rejections (``Overloaded`` at
      admission, ``DeadlineExceeded`` at dequeue, ``CircuitOpen`` from
      the breaker) while accepted requests stay bitwise-correct.
"""
import time
from concurrent.futures import TimeoutError as FutureTimeoutError

import jax
import numpy as np
import pytest

from repro.configs import get, tiny_variant
from repro.serving import (
    CircuitBreaker,
    CircuitOpen,
    DeadlineExceeded,
    EngineCache,
    FaultInjector,
    MicroBatcher,
    Overloaded,
    Rejected,
    RetryPolicy,
    Server,
    StreamSession,
    TransientFailure,
)
from repro.serving.server import Server as ServerClass

KEY = jax.random.key(11)
RESNET = tiny_variant(get("resnet18"))
MOBILENET = tiny_variant(get("mobilenet_v2"))


def _img(i=0, size=32):
    return jax.random.normal(jax.random.fold_in(KEY, i), (size, size, 3))


@pytest.fixture(scope="module")
def engine():
    """One tuned engine shared by the batcher-level chaos tests (builds
    are the expensive part; the batcher never mutates its engine unless
    a degrade hook is wired, and these tests don't wire one)."""
    eng = EngineCache(capacity=2).get(RESNET)
    eng.run(_img())  # warm the jit outside the timed/faulted windows
    return eng


# ----------------------------------------------------------------------
# the injector itself: the script must be exact and reproducible


def test_faultinjector_script_is_deterministic_and_exact():
    fi = (FaultInjector()
          .fail("dispatch", 1, 3)
          .delay("dispatch", 2, seconds=0.5)
          .fail_from("build", 2, error=RuntimeError, message="persistent"))
    assert fi.check("dispatch") == 0.0                   # index 0: clean
    with pytest.raises(TransientFailure):                # index 1: scripted
        fi.check("dispatch")
    assert fi.check("dispatch") == 0.5                   # index 2: delay
    with pytest.raises(TransientFailure):
        fi.check("dispatch")                             # index 3
    assert fi.check("dispatch") == 0.0                   # index 4: clean
    assert fi.check("build") == 0.0 and fi.check("build") == 0.0
    for _ in range(3):                                   # persistent tail
        with pytest.raises(RuntimeError, match="persistent"):
            fi.check("build")
    assert fi.count("dispatch") == 5 and fi.count("build") == 5
    assert fi.log == [("dispatch", 1, "error"), ("dispatch", 2, "delay"),
                      ("dispatch", 3, "error"), ("build", 2, "error"),
                      ("build", 3, "error"), ("build", 4, "error")]
    fi.clear("build")
    assert fi.check("build") == 0.0  # script dropped, counter survived
    assert fi.count("build") == 6


# ----------------------------------------------------------------------
# (a) transient faults: retried, resolved, bitwise


def test_transient_dispatch_fault_retried_bitwise(engine):
    fi = FaultInjector().fail("dispatch", 0)  # first attempt only
    with MicroBatcher(engine, max_batch=1, window_ms=1.0, faults=fi,
                      retry=RetryPolicy(max_retries=2, backoff_s=1e-4)) as b:
        out = b.submit(_img()).result(60.0)
    assert np.array_equal(np.asarray(out), np.asarray(engine.run(_img())))
    st = b.stats()
    assert st["retries"] == 1
    assert st["breaker"]["state"] == "closed"
    assert st["breaker"]["consecutive_failures"] == 0  # success reset it
    assert fi.count("dispatch") == 2  # the retry re-checked the site


def test_transient_chaos_every_accepted_future_resolves(engine):
    """Acceptance (a) end to end: sporadic transient faults across a
    request stream — zero unresolved futures, all outputs bitwise."""
    fi = FaultInjector().fail("dispatch", 1, 4, 5)  # 4,5: double fault
    with MicroBatcher(engine, max_batch=1, window_ms=1.0, faults=fi,
                      retry=RetryPolicy(max_retries=2, backoff_s=1e-4)) as b:
        futs = [(i, b.submit(_img(i))) for i in range(6)]
        outs = [(i, f.result(60.0)) for i, f in futs]
    for i, out in outs:
        assert np.array_equal(np.asarray(out),
                              np.asarray(engine.run(_img(i)))), i
    assert b.stats()["retries"] == 3


def test_retry_exhaustion_surfaces_the_transient_error(engine):
    fi = FaultInjector().fail("dispatch", 0, 1, 2)  # one fault too many
    with MicroBatcher(engine, max_batch=1, window_ms=1.0, faults=fi,
                      retry=RetryPolicy(max_retries=2, backoff_s=1e-4)) as b:
        fut = b.submit(_img())
        with pytest.raises(TransientFailure):
            fut.result(60.0)
    assert b.stats()["retries"] == 2  # both retries were spent


# ----------------------------------------------------------------------
# (b) persistent faults: breaker, degraded mode


def test_persistent_fault_trips_breaker_then_sheds_circuit_open(engine):
    """Without a degrade hook, the breaker's open state is the backstop:
    consecutive failures trip it, then requests shed fast and typed."""
    fi = FaultInjector().fail_from("dispatch", 0, error=RuntimeError,
                                   message="sick tuned kernel")
    with MicroBatcher(engine, max_batch=1, window_ms=1.0, faults=fi,
                      retry=RetryPolicy(max_retries=0),
                      breaker=CircuitBreaker(threshold=3,
                                             reset_s=3600.0)) as b:
        errs = []
        for i in range(5):
            try:
                b.submit(_img(i)).result(60.0)
            except Exception as e:
                errs.append(e)
    assert len(errs) == 5
    assert all(isinstance(e, RuntimeError) for e in errs[:3])
    assert all(isinstance(e, CircuitOpen) for e in errs[3:])
    st = b.stats()
    assert st["breaker"] == {"state": "open", "consecutive_failures": 3,
                             "threshold": 3, "trips": 1}
    assert st["shed"]["breaker"] == 2
    assert fi.count("dispatch") == 3  # open breaker never reaches dispatch


def test_failed_degrade_is_logged_and_sheds(engine, caplog):
    """A degrade hook that itself raises leaves the breaker open (typed
    shedding), and the hook's failure is logged, not swallowed."""
    import logging

    def broken_degrade():
        raise RuntimeError("fallback build failed")

    fi = FaultInjector().fail_from("dispatch", 0, error=RuntimeError,
                                   message="sick tuned kernel")
    with caplog.at_level(logging.WARNING, logger="repro.serving"):
        with MicroBatcher(engine, max_batch=1, window_ms=1.0, faults=fi,
                          retry=RetryPolicy(max_retries=0),
                          breaker=CircuitBreaker(threshold=1,
                                                 reset_s=3600.0),
                          degrade=broken_degrade) as b:
            first = b.submit(_img(0))
            with pytest.raises(RuntimeError, match="sick tuned kernel"):
                first.result(60.0)
            with pytest.raises(CircuitOpen):
                b.submit(_img(1)).result(60.0)
    assert b.stats()["degraded"] == 0
    failed = [r for r in caplog.records if "degrade" in r.getMessage()]
    assert failed and failed[0].levelno == logging.WARNING
    assert "fallback build failed" in caplog.text  # the traceback rides along


def test_breaker_half_open_probe_cycle():
    t = [0.0]
    br = CircuitBreaker(threshold=2, reset_s=10.0, clock=lambda: t[0])
    assert br.allow() and br.state == "closed"
    br.record_failure()
    assert not br.record_failure() is False  # second failure trips
    assert br.state == "open" and not br.allow()
    t[0] = 10.0  # cooldown elapsed
    assert br.state == "half_open"
    assert br.allow()          # exactly one probe
    assert not br.allow()      # concurrent dispatches still shed
    br.record_failure()        # probe failed: re-open for a full cooldown
    assert br.state == "open" and not br.allow()
    t[0] = 20.0
    assert br.allow()
    br.record_success()        # probe succeeded: closed again
    assert br.state == "closed" and br.allow()
    assert br.trips == 1  # the half-open re-open is not a fresh trip


def test_server_persistent_fault_degrades_to_xla_and_keeps_serving():
    """Acceptance (b): the full server path — persistent dispatch faults
    trip the breaker, the batcher swaps in the cache's xla-fallback
    rebuild, serving continues, and ``Server.stats()`` says so."""
    fi = FaultInjector().fail_from("dispatch", 0, error=RuntimeError,
                                   message="persistent kernel fault")
    server = Server(tiny=True, max_batch=1, window_ms=1.0, faults=fi,
                    breaker_threshold=3, retry=RetryPolicy(max_retries=0))
    ref = Server(tiny=True, max_batch=1, window_ms=1.0)
    try:
        ref_out = np.asarray(ref.run("resnet18", _img(), timeout=120.0))
        outs, failures = [], 0
        for _ in range(4):
            try:
                outs.append(server.run("resnet18", _img(), timeout=120.0))
            except RuntimeError:
                failures += 1
        # threshold-1 requests fail; the tripping one degrades and serves
        assert failures == 2 and len(outs) == 2
        st = server.stats()
        assert st["degraded"] == 1
        assert st["cache"]["degraded_keys"], "cache must flag the key"
        (batcher_stats,) = st["networks"].values()
        assert batcher_stats["degraded"] == 1
        assert batcher_stats["breaker"]["state"] == "closed"  # reset
        # the rebuilt engine runs every conv site on the xla escape hatch
        (key,) = server._batchers.keys()
        plan = server._batchers[key].engine.plan
        assert plan.choices and all(c.algorithm == "xla"
                                    for c in plan.choices.values())
        # same params, algorithm route only: outputs match the tuned ref
        for out in outs:
            np.testing.assert_allclose(np.asarray(out), ref_out, atol=1e-4)
    finally:
        server.close()
        ref.close()


def test_engine_cache_build_transient_fault_is_retried():
    fi = FaultInjector().fail("build", 0)
    cache = EngineCache(capacity=2, faults=fi,
                        retry=RetryPolicy(max_retries=2, backoff_s=1e-4))
    eng = cache.get(RESNET)
    assert np.asarray(eng.run(_img())).ndim == 1
    assert cache.build_retries == 1
    assert cache.degraded == 0
    assert fi.count("build") == 2


def test_engine_cache_plan_deploy_failure_falls_back_to_xla():
    """A rebuild that persistently fails while deploying a cached plan
    must come up degraded (xla-only plan) rather than fail the key."""
    fi = FaultInjector()
    cache = EngineCache(capacity=1, faults=fi,
                        retry=RetryPolicy(max_retries=1, backoff_s=1e-4))
    cache.get(RESNET)        # tunes + caches the plan
    cache.get(MOBILENET)     # capacity 1: evicts the resnet engine
    assert cache.evictions == 1
    fi.fail_from("plan_deploy", 0, error=RuntimeError,
                 message="deploy rejected")
    eng = cache.get(RESNET)  # rebuild deploys the cached plan -> fault
    assert all(c.algorithm == "xla" for c in eng.plan.choices.values())
    assert np.asarray(eng.run(_img())).ndim == 1
    assert cache.degraded == 1
    assert cache.stats()["degraded_keys"]


# ----------------------------------------------------------------------
# (c) overload: typed shedding, accepted requests stay correct


def test_overload_sheds_typed_and_accepted_stay_bitwise(engine):
    """2x+-capacity burst against a bounded queue: the excess is rejected
    with ``Overloaded`` at admission, and every accepted request resolves
    bitwise-equal to the unfaulted engine."""
    fi = FaultInjector().delay_from("dispatch", 0, seconds=0.1)
    with MicroBatcher(engine, max_batch=1, window_ms=0.5, max_queue=2,
                      faults=fi) as b:
        accepted, rejected = [], 0
        for i in range(10):  # burst far beyond queue + in-flight capacity
            try:
                accepted.append((i, b.submit(_img(i))))
            except Overloaded:
                rejected += 1
        results = [(i, f.result(120.0)) for i, f in accepted]
    assert rejected >= 1 and len(accepted) + rejected == 10
    assert b.stats()["shed"]["overload"] == rejected
    for i, out in results:  # faults delay, never corrupt
        assert np.array_equal(np.asarray(out),
                              np.asarray(engine.run(_img(i)))), i


def test_expired_requests_shed_at_dequeue_before_compute(engine):
    fi = FaultInjector().delay_from("dispatch", 0, seconds=0.15)
    with MicroBatcher(engine, max_batch=1, window_ms=0.5, deadline_ms=40.0,
                      faults=fi) as b:
        first = b.submit(_img(0))     # dequeued fresh, holds the loop
        late = [b.submit(_img(i)) for i in (1, 2)]  # expire while queued
        assert first.result(120.0) is not None
        for f in late:
            with pytest.raises(DeadlineExceeded, match="shed at dequeue"):
                f.result(120.0)
    assert b.stats()["shed"]["deadline"] == 2
    # the shed requests never reached dispatch: only request 0 was checked
    assert fi.count("dispatch") == 1


def test_cancelled_request_sheds_at_dequeue(engine):
    fi = FaultInjector().delay_from("dispatch", 0, seconds=0.15)
    with MicroBatcher(engine, max_batch=1, window_ms=0.5, faults=fi) as b:
        first = b.submit(_img(0))
        req = b.submit_request(_img(1))  # queued behind the slow dispatch
        req.cancel()
        first.result(120.0)
        with pytest.raises(DeadlineExceeded, match="cancelled"):
            req.future.result(120.0)
    assert b.stats()["shed"]["cancelled"] == 1
    assert fi.count("dispatch") == 1


def test_server_run_timeout_cancels_the_queued_request():
    """``Server.run(timeout=...)`` must actually cancel on timeout: the
    timed-out request is shed at dequeue instead of computed for nobody."""
    fi = FaultInjector().delay_from("dispatch", 0, seconds=0.3)
    server = Server(tiny=True, max_batch=1, window_ms=0.5, faults=fi)
    try:
        server.warm("resnet18")
        blocker = server.submit("resnet18", _img(0))  # occupies the loop
        with pytest.raises(FutureTimeoutError):
            server.run("resnet18", _img(1), timeout=0.02)
        blocker.result(120.0)
        deadline = time.perf_counter() + 30.0
        while time.perf_counter() < deadline:  # the shed happens at the
            (bs,) = server.stats()["networks"].values()  # loop's dequeue
            if bs["shed"]["cancelled"]:
                break
            time.sleep(0.01)
        assert bs["shed"]["cancelled"] == 1
        assert fi.count("dispatch") == 1  # never dispatched the dead one
    finally:
        server.close()


def test_server_close_is_idempotent_and_rejects_typed():
    server = Server(tiny=True)
    server.close()
    server.close()  # second close: no-op, no deadlock
    with pytest.raises(Overloaded):
        server.submit("resnet18", _img())
    with pytest.raises(Rejected):  # the typed hierarchy callers catch
        server.open_stream("resnet18", sim_compute_s=0.01)
    # Overloaded is still a RuntimeError: pre-resilience callers that
    # caught RuntimeError keep working unchanged
    with pytest.raises(RuntimeError):
        server.submit("resnet18", _img())


def test_stats_key_includes_dtype():
    """fp32 and bf16 variants of one network must not collide in
    ``Server.stats()`` (the old key was (network, input_size) only)."""
    key32 = ("resnet18-tiny", 32, "cpu", "float32", "float32")
    key16 = ("resnet18-tiny", 32, "cpu", "bfloat16", "bfloat16")
    mixed = ("resnet18-tiny", 32, "cpu", "float32", "bfloat16")
    assert ServerClass._stats_key(key32) == "resnet18-tiny/32/float32"
    assert ServerClass._stats_key(key16) == "resnet18-tiny/32/bfloat16"
    assert ServerClass._stats_key(mixed) == \
        "resnet18-tiny/32/float32/params=bfloat16"
    assert len({ServerClass._stats_key(k)
                for k in (key32, key16, mixed)}) == 3


# ----------------------------------------------------------------------
# streams under chaos (simulated clock: exact, repeatable accounting)


def _sim_stream(cache, faults, n_frames=6, sim_compute_s=0.008):
    session = StreamSession(cache.lease(RESNET), fps=30.0,
                            sim_compute_s=sim_compute_s, name="chaos",
                            faults=faults)
    frames = [session.submit_frame(_img(i)) for i in range(n_frames)]
    session.close()
    return session, frames


def test_stream_frame_fault_settles_frame_and_stream_survives():
    cache = EngineCache(capacity=2)
    fi = FaultInjector().fail("frame", 1, error=RuntimeError,
                              message="frame executor fault")
    session, frames = _sim_stream(cache, fi)
    with pytest.raises(RuntimeError, match="frame executor fault"):
        frames[1].future.result(60.0)
    assert frames[1].missed and not frames[1].dropped
    for f in frames[:1] + frames[2:]:  # every other frame resolved
        assert np.asarray(f.future.result(60.0)).ndim == 1
    st = session.stats()
    assert st["frames"] == len(frames)
    assert st["deadline_misses"] == 1


def test_stream_injected_latency_spike_misses_deterministically():
    """A scripted latency spike joins the simulated compute charge as
    pure arithmetic: the same script yields the exact same per-frame
    done-times and miss set on every run."""
    def run():
        cache = EngineCache(capacity=2)
        fi = FaultInjector().delay("frame", 2, seconds=0.05)
        session, frames = _sim_stream(cache, fi)
        return session.stats(), [(f.done, f.missed) for f in frames]

    stats_a, ledger_a = run()
    stats_b, ledger_b = run()
    assert ledger_a == ledger_b  # bit-exact repeatability
    assert stats_a["deadline_misses"] == stats_b["deadline_misses"] == 1
    done, missed = ledger_a[2]
    assert missed
    # the spike is charged arithmetically: done == arrival + compute + 0.05
    period = 1.0 / 30.0
    assert done == pytest.approx(2 * period + 0.008 + 0.05, abs=1e-12)
    assert all(not m for (_, m) in ledger_a[:2] + ledger_a[3:])
