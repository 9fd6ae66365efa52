"""The program's spans on the device trace's clock, and the readings made
from them, on synthetic spans whose answers are worked out by hand."""
import pytest

import bench_tiny  # noqa: F401  (paths)
from benchlib import hostspans as H
from benchlib import trace as T
from repro.serving.spans import Span

MS = 1_000_000


def _trace():
    """The device window is [0, 100) ns, busy [10, 30), [50, 65), [95, 100):
    idle gaps (0, 10), (30, 50), (65, 95)."""
    ops = [T.Event(10, 30, "conv"), T.Event(50, 65, "copy"),
           T.Event(95, 100, "fusion")]
    modules = [T.Event(-5, 0, T.OPEN_MARK), T.Event(9, 30, "forward"),
               T.Event(50, 65, "squeeze"), T.Event(95, 100, "forward"),
               T.Event(100, 101, T.CLOSE_MARK)]
    return T.window_between_marks(ops, modules)


def _one_dispatch_on_host():
    """One request (id 1) and its dispatch (id 9), host ns from 1000."""
    d = dict(id=9, parents=(1,))
    return [Span("serve.request", 1000, 1060, 1),
            Span("batcher.wait", 1000, 1004, 1),
            Span("batcher.window", 1001, 1004, **d),
            Span("scheduler.queue", 1004, 1006, **d),
            Span("engine.inputs", 1006, 1008, **d),
            Span("engine.call", 1008, 1040, **d),
            Span("engine.outputs", 1040, 1045, **d),
            Span("engine.ready", 1045, 1058, **d),
            Span("scheduler.return", 1058, 1059, **d),
            Span("batcher.resolve", 1059, 1061, **d)]


def test_one_dispatch_keeps_the_open_marks_offset():
    tr = _trace()
    # one engine.ready in the window: nothing to fit, the open mark's end
    # on the device (0) less its host stamp (1000) carries the spans
    marks = {H.OPEN: 1000, H.CLOSE: 1103}
    moved, error_us, shift_us = H.to_device_clock(
        _one_dispatch_on_host(), marks, tr)
    assert error_us is None and shift_us == 0
    assert (moved[0].t0_ns, moved[0].t1_ns) == (0, 60)
    assert moved[5].name == "engine.call" and moved[5].id == 9
    assert (moved[5].t0_ns, moved[5].t1_ns) == (8, 40)


def test_the_offset_is_fitted_to_the_ready_ends_round_by_round():
    """Programs end on the device at 20, 50 and 90; the host sees four
    dispatches finish at 1040, 1071, 1111 and 1112, the open mark's offset
    (-1000) putting them 19 ns late."""
    ops = [T.Event(15, 20, "conv"), T.Event(45, 50, "conv"),
           T.Event(85, 90, "conv")]
    modules = [T.Event(-5, 0, T.OPEN_MARK), *ops,
               T.Event(120, 121, T.CLOSE_MARK)]
    tr = T.window_between_marks(ops, modules)
    ready = [Span("engine.ready", t - 3, t, i, (i,))
             for i, t in enumerate((1040, 1071, 1111, 1112))]
    moved, error_us, shift_us = H.to_device_clock(
        ready, {H.OPEN: 1000, H.CLOSE: 1120}, tr)
    # round 1: nearest ends 50, 90, 90, 90 give lags -10, -19, 21, 22,
    # median 5.5 -> 6; round 2: 34, 65, 105, 106 against 20, 50, 90, 90
    # give 14, 15, 15, 16, median 15; round 3: lags -1, 0, 0, 1, done
    assert shift_us == pytest.approx(-0.021)
    assert [s.t1_ns for s in moved] == [19, 50, 90, 91]
    # quartiles of -1, 0, 0, 1: -0.75 and 0.75
    assert error_us == pytest.approx(0.0015)


def test_idle_by_span_gives_each_idle_instant_to_one_span():
    tr = _trace()
    moved, _, _ = H.to_device_clock(_one_dispatch_on_host(),
                                    {H.OPEN: 1000, H.CLOSE: 1100}, tr)
    got = {k: round(v * 1e9) for k, v in H.idle_by_span(tr, moved)}
    # (0, 10): wait 0-1, window 1-4, queue 4-6, inputs 6-8, call 8-10;
    # (30, 50): call 30-40, outputs 40-45, ready 45-50; (65, 95): nothing
    assert got == {"no span": 30, "engine.call": 12, "engine.outputs": 5,
                   "engine.ready": 5, "batcher.window": 3,
                   "scheduler.queue": 2, "engine.inputs": 2,
                   "batcher.wait": 1}
    assert sum(got.values()) == tr.window_ns - tr.busy_ns()
    assert H.idle_by_span(tr, []) == [["no span", 60e-9]]


def _two_requests():
    """Requests 1 and 2 in a 10 ms window, each in its own dispatch (100,
    101); request 3 and an engine build in set-up, before the window."""
    def dispatch(did, rid, t, queue, call):
        d = dict(id=did, parents=(rid,))
        steps = [("scheduler.queue", queue), ("engine.inputs", .1 * MS),
                 ("engine.call", call), ("engine.outputs", .1 * MS),
                 ("engine.ready", .2 * MS), ("scheduler.return", .1 * MS),
                 ("batcher.resolve", .1 * MS)]
        out = []
        for name, length in steps:
            out.append(Span(name, int(t), int(t + length), **d))
            t += length
        return out
    return [Span("engine.build", -3000 * MS, -1000 * MS, 50),
            Span("engine.first_call", -500 * MS, -200 * MS, 51, (3,)),
            Span("serve.request", -600 * MS, -100 * MS, 3),
            Span("batcher.wait", -600 * MS, -590 * MS, 3),
            Span("batcher.wait", 1 * MS, 3 * MS, 1),
            *dispatch(100, 1, 3 * MS, .2 * MS, .5 * MS),
            Span("serve.request", 1 * MS, int(4.25 * MS), 1),
            Span("batcher.wait", 5 * MS, 6 * MS, 2),
            *dispatch(101, 2, 6 * MS, .5 * MS, 1 * MS),
            Span("serve.request", 5 * MS, int(8.2 * MS), 2),
            Span("engine.first_call", 9 * MS, 9 * MS + 1, 102, (4,))]


def test_readers_over_the_windows_requests_and_dispatches():
    spans = _two_requests()
    marks = {H.OPEN: 0, H.CLOSE: 10 * MS}
    held = {1: int(4.5 * MS), 2: int(8.4 * MS), 3: -50 * MS}
    assert H.batcher_wait_ms(spans, marks) == pytest.approx(1.5)
    # inputs + call + outputs: 0.7 and 1.2 ms
    assert H.host_dispatch_ms(spans, marks) == pytest.approx(0.95)
    # queue + return + future -> client: 0.2 + 0.1 + 0.25, 0.5 + 0.1 + 0.2
    assert H.handoff_ms(spans, marks, held) == pytest.approx(0.675)
    # set-up's build and first call; not the one inside the window
    assert H.engine_build_s(spans, marks) == pytest.approx(2.3)
    medians = H.span_medians_ms(spans, marks)
    assert medians["scheduler.queue"] == pytest.approx(0.35)
    assert medians["serve.request"] == pytest.approx((3.25 + 3.2) / 2)
    assert H.dispatch_counters(spans, marks) == {"dispatches": 2}
    empty = {H.OPEN: 20 * MS, H.CLOSE: 30 * MS}
    assert H.batcher_wait_ms(spans, empty) is None
    assert H.host_dispatch_ms(spans, empty) is None
    assert H.handoff_ms(spans, empty, held) is None


def test_a_tiny_recorded_run_on_the_cpu_reads_what_the_host_can():
    import span_readings

    cell, pcfg = bench_tiny.tiny_cell("resnet18.c16", "resnet18-224-fp32")
    out = span_readings.readings(cell, 2**31 + 7, 0.5, True,
                                 program_cfg=pcfg)
    for key in ("batcher_wait_ms", "handoff_ms", "host_dispatch_ms",
                "engine_build_s"):
        assert out[key] is not None and out[key] > 0, key
    counters = out["dispatch_counters"]
    assert counters["batch"] >= counters["dispatches"] > 0
    assert counters["h2d_bytes"] == counters["padded"] * 32 * 32 * 3 * 4
    # the CPU has no device trace: no clock to map onto, no idle to cut
    for key in ("span_clock_error_us", "idle_by_span", "device_idle"):
        assert key not in out
    # and the script itself refuses to print readings without one
    with pytest.raises(SystemExit) as exit_:
        span_readings.main(["--workload", "resnet18.b1", "--seeds", "1"])
    assert exit_.value.code == 2
    off = span_readings.readings(cell, 2**31 + 7, 0.5, False,
                                 program_cfg=pcfg)
    assert "batcher_wait_ms" not in off and off["throughput_img_s"] > 0
