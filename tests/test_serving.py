"""Serving subsystem tests: engine cache, micro-batcher, end-to-end server.

The correctness bar is the issue's: concurrent single-image requests
through the micro-batching server must produce outputs *bitwise-equal* to
sequential tuned-engine runs — batching may change scheduling, never
numerics — and the LRU engine cache must return the identical engine
(jitted exactly once) for a repeated (network, input_size, device, dtype).
"""
import threading

import jax
import numpy as np
import pytest

from repro.configs import get, tiny_variant
from repro.core import InferenceEngine
from repro.core import engine as engine_mod
from repro.serving import EngineCache, MicroBatcher, Server, bucket, engine_key

KEY = jax.random.key(7)
RESNET = tiny_variant(get("resnet18"))
MOBILENET = tiny_variant(get("mobilenet_v2"))


def _images(n, size=32):
    return [jax.random.normal(jax.random.fold_in(KEY, i), (size, size, 3))
            for i in range(n)]


# ----------------------------------------------------------------------
# engine cache


def test_cache_hit_returns_identical_engine_jit_once(monkeypatch):
    """Same (network, input_size, device, dtype) -> the same engine object,
    with jax.jit invoked only for the single build (spy-counted)."""
    real_jit = jax.jit
    jit_calls = []

    def counting_jit(*args, **kwargs):
        jit_calls.append(args)
        return real_jit(*args, **kwargs)

    monkeypatch.setattr(engine_mod.jax, "jit", counting_jit)
    cache = EngineCache(capacity=2)
    e1 = cache.get(RESNET)
    n_build = len(jit_calls)
    assert n_build >= 1  # the engine's forward(s) were jitted
    e2 = cache.get(RESNET)
    assert e2 is e1  # identical object: same jit, same params, same plan
    assert len(jit_calls) == n_build  # hit jits nothing
    assert (cache.hits, cache.misses) == (1, 1)


def test_cache_distinct_keys_miss():
    cache = EngineCache(capacity=4)
    e1 = cache.get(RESNET)
    e2 = cache.get(MOBILENET)
    assert e1 is not e2
    assert cache.misses == 2 and cache.hits == 0
    assert engine_key(RESNET) != engine_key(MOBILENET)
    assert len(cache) == 2


def test_cache_lru_evicts_beyond_capacity():
    cache = EngineCache(capacity=1)
    e1 = cache.get(RESNET)
    cache.get(MOBILENET)  # evicts the resnet engine
    assert cache.evictions == 1
    assert MOBILENET in cache and RESNET not in cache
    e3 = cache.get(RESNET)  # rebuilt: a fresh object...
    assert e3 is not e1
    # ...but through the plan-reuse hook: same geometry -> the cached
    # TuningPlan is handed to the new engine instead of re-tuning
    assert e3.plan is e1.plan


def test_cache_plan_reuse_across_dtype_variants():
    """(network, input_size, compute_dtype) keys the plan. A variant
    differing only in param *storage* dtype shares the tuned plan (it was
    tuned for the compute dtype, which is what the kernels stream); a
    variant with a different *compute* dtype must NOT — its ConvSpecs
    carry the dtype and its byte traffic differs. The seed keyed plans by
    geometry alone, silently deploying fp32 choices onto bf16 engines."""
    from repro.core import with_precision

    cache = EngineCache(capacity=4)
    e32 = cache.get(RESNET)
    e_store16 = cache.get(RESNET.replace(param_dtype="bfloat16"))
    assert e_store16 is not e32  # distinct engine cache entries
    assert e_store16.plan is e32.plan  # storage-only variant: no re-tune

    e_bf16 = cache.get(with_precision(RESNET, "bfloat16"))
    assert e_bf16 is not e32
    assert e_bf16.plan is not e32.plan  # compute dtype gets its own plan
    assert {s.dtype for s in e_bf16.plan.specs.values()} == {"bfloat16"}
    assert {s.dtype for s in e32.plan.specs.values()} == {"float32"}
    assert cache.misses == 3


# ----------------------------------------------------------------------
# micro-batcher


def test_bucket_powers_of_two():
    assert [bucket(n, 8) for n in range(1, 9)] == [1, 2, 4, 4, 8, 8, 8, 8]
    assert bucket(3, 3) == 3  # cap wins over the power of two


def test_batcher_matches_sequential_bitwise_with_ragged_tail():
    """6 requests through a max_batch=4 batcher -> one full batch + one
    ragged batch of 2, all bitwise-equal to sequential engine.run."""
    eng = InferenceEngine(RESNET)
    imgs = _images(6)
    seq = [np.asarray(eng.run(im)) for im in imgs]
    with MicroBatcher(eng, max_batch=4, window_ms=250.0) as b:
        futs = [b.submit(im) for im in imgs]
        outs = [np.asarray(f.result(timeout=600)) for f in futs]
    for s, o in zip(seq, outs):
        assert np.array_equal(s, o)  # bitwise, not allclose
    sizes = sorted(d["batch"] for d in b.dispatches)
    assert sum(sizes) == 6
    assert sizes[-1] > 1  # traffic actually coalesced
    if sizes == [2, 4]:  # the expected split: full batch + ragged tail
        ragged = next(d for d in b.dispatches if d["batch"] == 2)
        assert ragged["padded"] == 2  # bucket(2) — padded, not max_batch


def test_batcher_single_request_takes_fast_path(monkeypatch):
    """A lone request must go through engine.run (the paper's single-image
    path), never the batched dispatch."""
    eng = InferenceEngine(RESNET)
    calls = []
    real_run, real_run_batch = eng.run, eng.run_batch
    monkeypatch.setattr(eng, "run",
                        lambda im: calls.append("run") or real_run(im))
    monkeypatch.setattr(eng, "run_batch",
                        lambda ims: calls.append("batch") or real_run_batch(ims))
    with MicroBatcher(eng, max_batch=4, window_ms=1.0) as b:
        out = b.submit(_images(1)[0]).result(timeout=600)
    assert calls == ["run"]
    assert out.shape == (RESNET.vocab_size,)


def test_batcher_padding_bounds_traces():
    """Ragged batch sizes pad to power-of-two buckets, so distinct traced
    batch shapes stay O(log max_batch) regardless of traffic pattern."""
    eng = InferenceEngine(RESNET)
    with MicroBatcher(eng, max_batch=4, window_ms=250.0) as b:
        for n in (3, 2, 3):  # three ragged bursts
            futs = [b.submit(im) for im in _images(n)]
            for f in futs:
                f.result(timeout=600)
    padded = {d["padded"] for d in b.dispatches if d["batch"] > 1}
    assert padded <= {2, 4}
    assert eng.trace_count() <= 2  # one per bucket, not one per batch size


def test_batcher_dispatch_error_resolves_futures():
    """A failing dispatch must surface on the futures, not kill the loop."""
    eng = InferenceEngine(RESNET)
    with MicroBatcher(eng, max_batch=2, window_ms=1.0) as b:
        bad = b.submit(jax.numpy.zeros((5, 5, 5, 5)))  # bogus image shape
        with pytest.raises(Exception):
            bad.result(timeout=600)
        ok = b.submit(_images(1)[0])  # loop survives and keeps serving
        assert ok.result(timeout=600).shape == (RESNET.vocab_size,)


# ----------------------------------------------------------------------
# server end-to-end


def test_server_concurrent_two_networks_bitwise():
    """N concurrent single-image submissions per network, one shared-cache
    server process, outputs bitwise-equal to sequential engine runs."""
    imgs = _images(5)
    truth = {}
    engines = {"resnet18": InferenceEngine(RESNET),
               "mobilenet_v2": InferenceEngine(MOBILENET)}
    for net, eng in engines.items():
        truth[net] = [np.asarray(eng.run(im)) for im in imgs]

    with Server(tiny=True, max_batch=4, window_ms=100.0) as server:
        for net in engines:
            server.warm(net)
        futures = {net: [None] * len(imgs) for net in engines}

        def client(net):
            for i, im in enumerate(imgs):
                futures[net][i] = server.submit(net, im)

        threads = [threading.Thread(target=client, args=(net,))
                   for net in engines]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        outs = {net: [np.asarray(f.result(timeout=600)) for f in fs]
                for net, fs in futures.items()}
        stats = server.stats()

    for net in engines:
        for s, o in zip(truth[net], outs[net]):
            assert np.array_equal(s, o)
    assert stats["cache"]["misses"] == 2  # one engine build per network
    assert len(stats["networks"]) == 2
    for b in stats["networks"].values():
        assert b["requests"] == len(imgs)


def test_server_submit_after_close_raises():
    server = Server(tiny=True)
    server.close()
    with pytest.raises(RuntimeError):
        server.submit("resnet18", _images(1)[0])
