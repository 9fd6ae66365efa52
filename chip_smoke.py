"""One-chip smoke run of the served single-image path at published widths.

Serves ResNet-18, MobileNetV2 and EfficientNet-B0 at 224x224 fp32 through
the normal entry points (``repro.serving.Server`` with the default
``ServingOptions`` -> ``MicroBatcher`` -> ``InferenceEngine`` with its
cost-model plan -> the Pallas kernels) on one TPU, from seeded random
weights and images, and checks what comes back:

  * every request resolves (none failed, none shed), and at least one
    micro-batch of two or more images was dispatched;
  * no plan has an ``xla`` site, each compiled forward contains a
    Pallas kernel (``tpu_custom_call``), and no engine was degraded to the
    XLA fallback plan;
  * every served logit vector matches a plain reference (the same network
    and weights with every conv on XLA, traced at HIGHEST matmul
    precision) within ``rel_tol()`` of the reference's largest logit, with
    the same top-1 class.

Run it from the root of a checkout on a machine with a TPU:

    python chip_smoke.py

It keeps JAX's compilation cache where ``JAX_COMPILATION_CACHE_DIR`` says,
or else in ``.jax_cache`` at the checkout root (``repro.compile_cache``).
Without a TPU, or without the repository beside it, it exits non-zero and
prints no result. The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Times printed here are one-off readings of this run on the host clock,
not a benchmark.
"""
from __future__ import annotations

import json
import os
import sys
import time

NETWORKS = ("resnet18", "mobilenet_v2", "efficientnet_b0")
SEED = 0
N_SINGLE = 4        # sequential requests per network
N_CONCURRENT = 4    # submitted together, so the batcher forms a batch >= 2
TIMEOUT_S = 900.0   # per request; the first ones of a network compile


def rel_tol():
    """Allowed max |served - reference| over the reference's largest
    |logit|: the bf16 row of the repository's kernel-vs-fp32-reference
    tolerance table (``repro.core.dtypes.tolerance``), 3e-2.

    Why bf16 for an fp32 forward: on a TPU v5e an fp32 jnp.dot inside a
    Pallas kernel runs as ONE bf16 pass, exactly like an XLA fp32 matmul
    at default precision — both operands rounded to 8 significant bits,
    products accumulated in fp32. ``dot_precision`` measures this every
    run (on v5e the Pallas product equals the one-pass bf16 product bit
    for bit, 2.6e-3 of the largest entry from HIGHEST). Every conv and
    the classifier head carry that rounding, as a bf16 forward's would.
    Three v5e runs, each with its own draw of weights, measured 2.9e-3 to
    3.4e-3 (ResNet-18) and 4.3e-3 to 7.3e-3 (MobileNetV2). A wrong tap,
    phase or epilogue in a kernel moves logits by tens of percent.
    """
    from repro.core.dtypes import tolerance

    return tolerance("bfloat16")


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def images(n, size, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.standard_normal((size, size, 3), dtype=np.float32)
            for _ in range(n)]


def dot_precision():
    """How far an fp32 Pallas jnp.dot and an XLA default-precision dot
    are from the HIGHEST-precision product, and from one bf16 pass."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kx, kw = jax.random.split(jax.random.key(SEED))
    x = jax.random.normal(kx, (256, 512), jnp.float32)
    w = jax.random.normal(kw, (512, 256), jnp.float32)

    def kernel(x_ref, w_ref, o_ref):
        o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                             preferred_element_type=jnp.float32)

    pallas = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32))(x, w)
    highest = jnp.dot(x, w, precision="highest")
    one_pass = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    default = jnp.dot(x, w)
    scale = float(jnp.max(jnp.abs(highest)))

    def rel(a, b):
        return float(jnp.max(jnp.abs(a - b))) / scale

    return {"pallas_vs_highest": rel(pallas, highest),
            "pallas_vs_bf16_pass": rel(pallas, one_pass),
            "xla_default_vs_highest": rel(default, highest),
            "xla_default_vs_bf16_pass": rel(default, one_pass)}


def has_kernel(hlo_text):
    return "tpu_custom_call" in hlo_text


def compile_forward(engine, image):
    """AOT-compile the engine's single-image forward; -> (seconds, hlo)."""
    t0 = time.perf_counter()
    compiled = engine._fwd.lower(engine.params, images=image[None],
                                 winograd_u=engine.winograd_u or None
                                 ).compile()
    return time.perf_counter() - t0, compiled.as_text()


def serve_and_check(server, network):
    """Warm ``network`` on ``server``, serve seeded requests, check them
    against the XLA reference; -> this network's report."""
    import jax
    import numpy as np

    from repro.core.engine import InferenceEngine

    rep = {"network": network}
    t0 = time.perf_counter()
    server.warm(network)
    rep["build_s"] = time.perf_counter() - t0
    cfg = server._resolve_cfg(network)
    engine = server.engines.get(cfg)
    size = cfg.extra["img"]
    plan = engine.plan
    xla_sites = sorted(n for n, c in {**plan.choices,
                                      **plan.block_choices}.items()
                       if c.algorithm == "xla")
    check(not xla_sites, f"{network}: plan has xla sites {xla_sites}")
    rep["plan"] = {"conv_sites": len(plan.choices),
                   "fused_blocks": len(plan.block_choices)}

    imgs = images(N_SINGLE + N_CONCURRENT, size, SEED)
    tol = rel_tol()
    rep["compile_s"], hlo = compile_forward(engine, imgs[0])
    check(has_kernel(hlo),
          f"{network}: compiled forward has no tpu_custom_call")

    # sequential single requests: the batch-1 fast path
    served, lat = [], []
    for img in imgs[:N_SINGLE]:
        t0 = time.perf_counter()
        served.append(np.asarray(server.submit(network, img)
                                 .result(timeout=TIMEOUT_S)))
        lat.append(time.perf_counter() - t0)
    rep["first_request_s"] = lat[0]
    rep["warm_request_s"] = sorted(lat[1:])
    # the engine's own forward, ended by block_until_ready
    run_lat = []
    for img in imgs[1:N_SINGLE]:
        t0 = time.perf_counter()
        jax.block_until_ready(engine.run(img))
        run_lat.append(time.perf_counter() - t0)
    rep["warm_engine_run_s"] = sorted(run_lat)

    # concurrent requests: coalesced into micro-batches
    t0 = time.perf_counter()
    tickets = [server.submit(network, img) for img in imgs[N_SINGLE:]]
    served += [np.asarray(t.result(timeout=TIMEOUT_S)) for t in tickets]
    rep["concurrent_s"] = time.perf_counter() - t0

    # the plain reference: same weights, every conv on XLA, HIGHEST
    ref_engine = InferenceEngine(cfg, params=engine.params, algorithm="xla")
    errs = []
    with jax.default_matmul_precision("highest"):
        for i, (img, got) in enumerate(zip(imgs, served)):
            want = np.asarray(ref_engine.run(img))
            check(got.shape == want.shape == (cfg.vocab_size,),
                  f"{network} request {i}: logits {got.shape}")
            check(np.isfinite(got).all(),
                  f"{network} request {i}: non-finite logits")
            err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
            errs.append(err)
            check(err <= tol,
                  f"{network} request {i}: logit error {err:.3e} of the "
                  f"largest reference logit > {tol:g}")
            check(int(got.argmax()) == int(want.argmax()),
                  f"{network} request {i}: top-1 {int(got.argmax())} != "
                  f"reference {int(want.argmax())}")
    rep["max_rel_logit_err"] = max(errs)

    # a second engine for the same network: its compile is a cache hit
    t0 = time.perf_counter()
    again = InferenceEngine(cfg, params=engine.params, plan=plan)
    rep["rebuild_s"] = time.perf_counter() - t0
    rep["recompile_s"], _ = compile_forward(again, imgs[0])
    return rep


def check_stats(stats, networks):
    """No degraded engine, no failed or shed request, and at least one
    dispatch of two or more images."""
    check(stats["cache"]["degraded"] == 0,
          f"engine cache degraded: {stats['cache']['degraded_keys']}")
    batched = False
    for key, net in stats["networks"].items():
        check(net["degraded"] == 0, f"{key}: batcher degraded")
        check(net["shed_total"] == 0, f"{key}: shed {net['shed']}")
        check(net["requests"] == N_SINGLE + N_CONCURRENT,
              f"{key}: {net['requests']} requests resolved, want "
              f"{N_SINGLE + N_CONCURRENT}")
        batched |= any(b >= 2 for b in net["batch_histogram"])
    check(len(stats["networks"]) == len(networks),
          f"served {sorted(stats['networks'])}, want {list(networks)}")
    check(batched, "no micro-batch of two or more images was dispatched")


def main():
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not 'tpu'; "
              f"this check runs only on the chip", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro import compile_cache
    from repro.serving import Server, ServingOptions

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}; compile cache: {compile_cache.enable()}",
          flush=True)
    prec = dot_precision()
    print(f"fp32 dot precision on this chip: {json.dumps(prec)}", flush=True)

    reports = []
    server = Server(options=ServingOptions())
    try:
        for network in NETWORKS:
            rep = serve_and_check(server, network)
            reports.append(rep)
            print(f"{network}: build {rep['build_s']:.3f}s, compile "
                  f"{rep['compile_s']:.3f}s first / {rep['recompile_s']:.3f}s"
                  f" on a second build (persistent cache); first request "
                  f"{rep['first_request_s']:.3f}s", flush=True)
            print(f"{network}: warm latency per request (chip, host clock, "
                  f"one-off smoke reading): served "
                  f"{[round(t, 6) for t in rep['warm_request_s']]} s, "
                  f"engine.run {[round(t, 6) for t in rep['warm_engine_run_s']]}"
                  f" s; max logit error {rep['max_rel_logit_err']:.3e} of "
                  f"the largest reference logit (limit {rel_tol():g})",
                  flush=True)
        stats = server.stats()
    finally:
        server.close()
    print("server.stats(): " + json.dumps(stats, default=str), flush=True)
    print("reports: " + json.dumps(reports), flush=True)
    check_stats(stats, NETWORKS)
    check(prec["pallas_vs_highest"] < rel_tol(),
          f"fp32 Pallas dot is {prec['pallas_vs_highest']:.3e} from HIGHEST")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
