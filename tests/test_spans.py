"""Spans on the served request path (``repro.serving.spans``): off, they
cost the serving code nothing in the spans module; on, one request yields
each span of the path once, on one request id across the client, batcher
and device threads."""
import threading
import tracemalloc

import numpy as np
import pytest

from repro.serving import Server, ServingOptions, spans

REQUEST_PATH = ("serve.request", "batcher.wait", "batcher.window",
                "scheduler.queue", "engine.inputs", "engine.call",
                "engine.outputs", "engine.ready", "scheduler.return",
                "batcher.resolve")
SETUP = ("engine.build", "engine.first_call")


def _img(v=0.5):
    return np.full((32, 32, 3), v, np.float32)


@pytest.fixture
def recording_off():
    assert spans.active is None
    yield
    if spans.active is not None:  # a failed test must not leak it on
        spans.active.stop()


def test_start_mark_stop(recording_off):
    rec = spans.start()
    assert spans.active is rec
    with pytest.raises(RuntimeError):
        spans.start()
    rec.add("x", 1, 2, 7, (3, 4), {"batch": 2})
    t = rec.mark("window.open")
    got = rec.stop()
    assert spans.active is None
    assert got == [spans.Span("x", 1, 2, 7, (3, 4), {"batch": 2})]
    assert rec.marks == {"window.open": t}
    rec.add("late", 3, 4, 8)  # a dispatch still in flight after stop()
    assert len(got) == 1
    spans.start().stop()  # a new recording can start


def test_recording_off_runs_no_code_of_spans_and_allocates_nothing(
        recording_off):
    with Server(tiny=True) as server:
        server.run("resnet18", _img())  # build and compile first
        ran = []

        def profile(frame, event, arg):
            if event == "call" and frame.f_code.co_filename == spans.__file__:
                ran.append(frame.f_code.co_name)
        tracemalloc.start()
        threading.setprofile_all_threads(profile)
        try:
            before = tracemalloc.take_snapshot()
            server.run("resnet18", _img(0.25))
            after = tracemalloc.take_snapshot()
        finally:
            threading.setprofile_all_threads(None)
            tracemalloc.stop()
    only_spans = [tracemalloc.Filter(True, spans.__file__)]
    grown = after.filter_traces(only_spans).compare_to(
        before.filter_traces(only_spans), "lineno")
    assert [s for s in grown if s.size_diff > 0] == []
    assert ran == []


def test_one_request_yields_each_span_once_on_one_id(recording_off):
    rec = spans.start()
    with Server(tiny=True) as server:
        server.run("resnet18", _img())
    got = rec.stop()
    names = sorted(s.name for s in got)
    assert names == sorted(REQUEST_PATH + SETUP)
    by = {s.name: s for s in got}
    assert all(s.t0_ns <= s.t1_ns for s in got)

    request = by["serve.request"]
    rid = request.id
    assert by["batcher.wait"].id == rid
    dispatch = {s.id for s in got if s.parents}
    assert len(dispatch) == 1 and rid not in dispatch
    assert all(s.parents == (rid,) for s in got
               if s.name not in ("serve.request", "batcher.wait",
                                 "engine.build"))
    # the build comes before the request; the request holds its path
    assert by["engine.build"].t1_ns <= request.t0_ns
    wait, window = by["batcher.wait"], by["batcher.window"]
    assert request.t0_ns <= wait.t0_ns <= window.t0_ns
    assert window.t1_ns <= wait.t1_ns
    path = [by[n] for n in REQUEST_PATH[2:]]
    for a, b in zip(path, path[1:]):
        assert a.t1_ns <= b.t0_ns, (a.name, b.name)
    assert wait.t1_ns <= by["scheduler.queue"].t0_ns
    assert by["batcher.resolve"].t0_ns <= request.t1_ns \
        <= by["batcher.resolve"].t1_ns
    first = by["engine.first_call"]
    assert (first.t0_ns, first.t1_ns) == (by["engine.call"].t0_ns,
                                          by["engine.ready"].t1_ns)
    assert by["engine.call"].attrs == {"batch": 1, "padded": 1,
                                       "h2d_bytes": _img().nbytes}


def test_a_padded_batch_of_three_is_one_dispatch_of_three_requests(
        recording_off):
    options = ServingOptions(max_batch=8, window_ms=300.0)
    with Server(tiny=True, options=options) as server:
        server.warm("resnet18")
        rec = spans.start()
        tickets = [server.submit("resnet18", _img(v)) for v in (.1, .2, .3)]
        for t in tickets:
            t.result(timeout=120)
        got = rec.stop()
    ids = tuple(t.id for t in tickets)
    calls = [s for s in got if s.name == "engine.call"]
    assert len(calls) == 1
    call = calls[0]
    assert call.parents == ids
    assert call.attrs == {"batch": 3, "padded": 4,
                          "h2d_bytes": 4 * _img().nbytes}
    assert sorted(s.id for s in got if s.name == "serve.request") \
        == sorted(ids)
    assert {s.id for s in got if s.parents} == {call.id}


@pytest.mark.parametrize("network,counters", [
    ("efficientnet_b0", {"sites": 13, "xla_sites": 0, "fused_sites": 0,
                         "gated_sites": 4}),
    ("mobilenet_v2", {"sites": 13, "xla_sites": 0, "fused_sites": 4,
                      "gated_sites": 0})])
def test_engine_build_carries_the_plans_counters(recording_off, network,
                                                 counters):
    rec = spans.start()
    with Server(tiny=True) as server:
        server.warm(network)
    got = rec.stop()
    (build,) = [s for s in got if s.name == "engine.build"]
    assert build.attrs == counters


def test_engine_counters_at_published_size():
    """EfficientNet-B0 at 224x224: every site on a kernel, the SE of all
    16 blocks gated, no block fused."""
    from repro.configs import get
    from repro.core import InferenceEngine

    eng = InferenceEngine(get("efficientnet_b0"), params={})
    assert eng.plan_counters() == {"sites": 49, "xla_sites": 0,
                                   "fused_sites": 0, "gated_sites": 16}
