"""Benchmark driver — one section per paper table/figure + the roofline.

  conv_memory      — paper Table 3 (memory traffic) reproduction
  conv_algorithms  — paper Fig. 5 (exec time across devices) cost-model
  conv_arith       — paper Table 4 (arithmetic profile) + interpret wall
  autotune         — the paper's tuning library on every ResNet layer
  roofline         — §Roofline table from the multi-pod dry-run artifacts

``--json PATH`` switches to the machine-readable emitter instead: it tunes
the tiny config end-to-end and writes one record per conv site (algorithm,
tuned params, cost-model estimates, ConvSpec flops/bytes, and an
interpret-mode proxy timing of the chosen kernel) so CI can track the perf
trajectory across PRs. ``--config`` picks the network (default resnet18).

``--serve PATH`` exercises the serving subsystem instead: concurrent
single-image requests for >= 2 networks through one micro-batching
``Server`` (one shared EngineCache process), reporting per-network
throughput, latency percentiles, and batch-size histograms to
BENCH_serving.json. CPU interpret-mode numbers are a trend line across
PRs, not absolute device performance.

``--stream PATH`` runs the streaming scenario: K concurrent 30 fps
simulated-clock streams (per-stream engine leases out of one shared
cache) alongside on-demand classify traffic, reporting per-stream
deadline-miss rate, drop rate, frame latency percentiles, and classify
contention to BENCH_streaming.json. The simulated-clock numbers are
deterministic, so CI gates on the miss rate (tools/compare_bench.py);
the wall-clock classify/contention numbers are an ungated trend line.

``--quant PATH`` runs the accuracy-vs-speed precision sweep: one fp32
reference engine, then bf16 / fp16 / int8-weight variants sharing the
same parameter values, each classifying the same image set. Per-precision
rows (top-1 agreement with fp32, max relative logit error, dtype-keyed
cost-model totals, weight storage bytes, xla fallback sites) go to
BENCH_quant.json; tools/compare_bench.py gates agreement drops and any
tuned-site -> xla fallback in low precision against the baseline.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _section(title):
    print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}", flush=True)


def _proxy_time(spec, choice, repeats=2):
    """Interpret-mode wall-clock of the site's chosen kernel (min of
    ``repeats`` after a warm-up) — a CPU proxy, not TPU time; useful as a
    trend line across PRs, not as an absolute number."""
    from repro.core.autotune import _synth_inputs
    from repro.kernels import ops, ref

    try:
        x, w = _synth_inputs(spec)
        if choice.algorithm == "xla":
            def run():
                return ref.conv2d_reference(x, w, stride=spec.stride,
                                            padding="VALID",
                                            groups=spec.groups)
        else:
            def run():
                return ops.dispatch(choice.algorithm, x, w, impl="pallas",
                                    stride=spec.stride,
                                    **dict(choice.params))
        run().block_until_ready()  # warm-up / compile
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            run().block_until_ready()
            ts.append(time.perf_counter() - t0)
        return min(ts)
    except Exception as e:  # pragma: no cover - robustness for CI smoke
        print(f"  proxy timing failed for {choice.algorithm} on {spec}: {e}",
              file=sys.stderr)
        return None


def emit_json(path, config="resnet18"):
    """Tune the tiny variant of ``config`` and dump the per-layer plan +
    proxy timings to ``path`` (the BENCH_conv.json CI artifact).

    Besides the per-conv ``layers`` rows, the artifact carries one
    ``blocks`` row per fusible block site — fused or not — comparing the
    fused megakernel's cost-model estimates against the per-layer
    constituent sum (plus the unfused shortcut-add pass where the block
    carries a residual). ``tools/compare_bench.py`` gates on these rows: a
    previously-fused site regressing to per-layer fails CI, and every
    fused row's byte estimate must sit below its per-layer sum.
    """
    from dataclasses import asdict

    from repro.configs import get, tiny_variant
    from repro.core import InferenceEngine, autotune

    cfg = tiny_variant(get(config))
    eng = InferenceEngine(cfg)
    plan = eng.plan
    layers = []
    for name, spec in plan.specs.items():
        ch = plan.choices[name]
        layers.append({
            "layer": name,
            "algorithm": ch.algorithm,
            "params": dict(ch.params),
            "est_time_s": ch.est_time,
            "est_bytes": ch.est_bytes,
            "est_flops": ch.est_flops,
            "vmem_bytes": ch.vmem,
            "flops": spec.flops,
            "bytes_min": spec.bytes_min,
            "interpret_time_s": _proxy_time(spec, ch),
            "spec": asdict(spec),
        })
    timed = [l["interpret_time_s"] for l in layers
             if l["interpret_time_s"] is not None]
    blocks = []
    for name, bspec in eng._block_specs():
        ch = plan.block_choices.get(name)
        per_layer_bytes = sum(
            c.est_bytes for c in autotune.block_constituents(
                bspec, epilogue=True)) + bspec.residual_pass_bytes
        blocks.append({
            "block": name,
            "kind": bspec.kind,
            "fused": ch is not None,
            "algorithm": ch.algorithm if ch else None,
            "params": dict(ch.params) if ch else {},
            "est_time_s": ch.est_time if ch else None,
            "est_bytes": ch.est_bytes if ch else None,
            "vmem_bytes": ch.vmem if ch else None,
            "per_layer_est_time_s": autotune.block_baseline_time(
                bspec, epilogue=True),
            "per_layer_est_bytes": per_layer_bytes,
            "saved_bytes": bspec.saved_bytes,
            "spec": asdict(bspec),
        })
    payload = {
        "config": cfg.name,
        "mode": plan.mode,
        "n_sites": len(layers),
        "algorithms": sorted({l["algorithm"] for l in layers}),
        "xla_sites": [l["layer"] for l in layers if l["algorithm"] == "xla"],
        "fused_sites": [b["block"] for b in blocks if b["fused"]],
        "totals": {
            "est_time_s": sum(l["est_time_s"] for l in layers),
            "est_bytes": sum(l["est_bytes"] for l in layers),
            "flops": sum(l["flops"] for l in layers),
            "interpret_time_s": sum(timed),
        },
        "layers": layers,
        "blocks": blocks,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {path}: {payload['n_sites']} sites "
          f"({', '.join(payload['algorithms'])}), "
          f"{len(payload['xla_sites'])} xla fallbacks, "
          f"{len(payload['fused_sites'])}/{len(blocks)} block sites fused")


def _serving_steady(networks, requests_per_net, max_batch, window_ms):
    """The throughput leg: interleaved single-image requests over >= 2
    networks through one shared-cache Server; every request must resolve."""
    import jax

    from repro.serving import Server, ServingOptions

    server = Server(tiny=True, options=ServingOptions(
        max_batch=max_batch, window_ms=window_ms))
    key = jax.random.key(0)
    img = jax.random.normal(key, (32, 32, 3))
    for net in networks:  # build + jit outside the timed window
        server.run(net, img)
    t0 = time.perf_counter()
    tickets = []
    for i in range(requests_per_net):  # interleave networks: the shared
        for net in networks:           # cache serves them side by side
            tickets.append(server.submit(
                net, jax.random.normal(jax.random.fold_in(key, i), (32, 32, 3))))
    for t in tickets:
        t.result(timeout=600)
    wall = time.perf_counter() - t0
    stats = server.stats()
    server.close()
    return {
        "requests": len(tickets),
        "requests_per_net": requests_per_net,
        "wall_s": wall,
        "throughput_rps": len(tickets) / wall,
        "per_network": stats["networks"],
        "cache": stats["cache"],
    }


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _serving_overload(network, *, offered=80, max_queue=4,
                      service_delay_s=0.04, submit_interval_s=0.005):
    """The overload leg: offer ~2x+ the server's capacity and prove the
    admission controller sheds the excess instead of queueing it.

    A ``FaultInjector`` latency fault pins the per-dispatch service time
    to a known floor (``service_delay_s``), so the offered load exceeds
    capacity regardless of machine speed; submissions arrive every
    ``submit_interval_s`` — well past capacity — against a ``max_queue``
    admission bound. The gate (tools/compare_bench.py, serving kind)
    holds: shed_rate within a band of the baseline AND nonzero, every
    accepted Future resolved (``unresolved == 0``), and accepted-request
    p95 latency under ``p95_bound_s`` — bounded queueing is the point:
    admission control converts unbounded latency into typed rejections.

    ``p95_bound_s`` is derived on the spot from the machine's measured
    warm service time: an admitted request waits for at most ``max_queue``
    requests ahead of it, so p95 must sit under ``(max_queue + 3) *
    service_s`` (one slot of in-flight slack, two of timer slop). The
    bound travels in the artifact, making the gate self-contained and
    machine-portable — what it pins is the *queue-depth invariant*, not an
    absolute wall-clock number.
    """
    import jax

    from repro.serving import FaultInjector, Overloaded, Server, ServingOptions

    faults = FaultInjector().delay_from("dispatch", 0,
                                        seconds=service_delay_s)
    server = Server(tiny=True, options=ServingOptions(
        max_batch=1, window_ms=0.5, max_queue=max_queue, faults=faults))
    key = jax.random.key(1)
    img = jax.random.normal(key, (32, 32, 3))
    server.warm(network)  # build outside the overloaded window
    # measure the warm per-request service time (injected floor + engine
    # compute on THIS machine); min of a few trials rejects scheduler noise
    service_s = min(
        _timed(lambda: server.run(network, img)) for _ in range(3))
    p95_bound_s = (max_queue + 3) * service_s
    tickets, shed = [], 0
    t0 = time.perf_counter()
    for i in range(offered):
        try:
            tickets.append(server.submit(network, img))
        except Overloaded:
            shed += 1
        time.sleep(submit_interval_s)
    unresolved = 0
    for t in tickets:
        try:
            t.result(timeout=600)
        except Exception:
            unresolved += 1  # an accepted request MUST resolve
    wall = time.perf_counter() - t0
    per_net = server.stats()["networks"]
    server.close()
    accepted = len(tickets)
    b = next(iter(per_net.values()))  # single-network scenario
    return {
        "offered": offered,
        "accepted": accepted,
        "shed": shed,
        "shed_rate": shed / offered,
        "unresolved": unresolved,
        "max_queue": max_queue,
        "service_delay_s": service_delay_s,
        "measured_service_s": service_s,
        "submit_interval_s": submit_interval_s,
        "wall_s": wall,
        "accepted_p50_s": b["latency_p50_s"],
        "accepted_p95_s": b["latency_p95_s"],
        "p95_bound_s": p95_bound_s,
        "shed_by_cause": b["shed"],
        "retries": b["retries"],
        "breaker": b["breaker"],
    }


def _serving_sweep(network, *, load_factors=(0.25, 0.5, 2.0),
                   n_requests=16, max_queue=4, service_delay_s=0.025):
    """The SLO-curve leg: an offered-QPS ladder against one server,
    per-rung p50/p95/p99 + shed rate — so the bench gate holds a latency
    curve, not one overload point.

    Like the overload leg, a ``FaultInjector`` latency fault pins the
    per-dispatch service time to a known floor, and capacity is
    *measured* on the spot (``capacity_qps = 1 / warm service time``), so
    the rungs are machine-portable: each rung offers
    ``load_factor * capacity_qps``, arrivals paced open-loop. The
    invariants the gate holds per rung:

      * **below saturation** (load_factor < 1): ``shed_rate == 0`` and
        p95 under the derived ``p95_bound_s`` — an unloaded server must
        not reject or queue;
      * **above saturation**: shedding engages (rate > 0) while accepted
        p95 stays bounded — the overload trade, now anchored to a curve;
      * **monotone shed** — shed_rate must not decrease as offered load
        rises: a non-monotone curve means admission control is load-
        dependent in the wrong direction;
      * every accepted request resolves (``unresolved == 0``), at every
        rung.
    """
    import jax

    from repro.serving import FaultInjector, Rejected, Server, ServingOptions

    faults = FaultInjector().delay_from("dispatch", 0,
                                        seconds=service_delay_s)
    server = Server(tiny=True, options=ServingOptions(
        max_batch=1, window_ms=0.5, max_queue=max_queue, faults=faults))
    key = jax.random.key(2)
    img = jax.random.normal(key, (32, 32, 3))
    server.warm(network)  # build + jit outside every timed rung
    service_s = min(
        _timed(lambda: server.run(network, img)) for _ in range(3))
    capacity_qps = 1.0 / service_s
    p95_bound_s = (max_queue + 3) * service_s
    rungs = []
    for lf in load_factors:
        offered_qps = lf * capacity_qps
        interval = 1.0 / offered_qps
        tickets, shed = [], 0
        t0 = time.perf_counter()
        for _ in range(n_requests):
            try:
                tickets.append(server.submit(network, img))
            except Rejected:
                shed += 1
            time.sleep(interval)
        lats, unresolved = [], 0
        for t in tickets:
            try:
                t.result(timeout=600)
                lats.append(t.latency)
            except Exception:
                unresolved += 1
        wall = time.perf_counter() - t0
        lats.sort()

        def pct(q):
            if not lats:
                return None
            return lats[min(len(lats) - 1,
                            round(q / 100 * (len(lats) - 1)))]

        rungs.append({
            "load_factor": lf,
            "offered_qps": offered_qps,
            "offered": n_requests,
            "accepted": len(tickets),
            "shed": shed,
            "shed_rate": shed / n_requests,
            "unresolved": unresolved,
            "p50_s": pct(50),
            "p95_s": pct(95),
            "p99_s": pct(99),
            "wall_s": wall,
        })
    stats = server.stats()
    server.close()
    return {
        "network": network,
        "n_requests": n_requests,
        "max_queue": max_queue,
        "service_delay_s": service_delay_s,
        "measured_service_s": service_s,
        "capacity_qps": capacity_qps,
        "p95_bound_s": p95_bound_s,
        "scheduler": stats["scheduler"],
        "rungs": rungs,
    }


def emit_serving_json(path, networks=("resnet18", "mobilenet_v2"),
                      requests_per_net=12, max_batch=4, window_ms=20.0):
    """Run the serving scenarios and dump BENCH_serving.json.

    Three scenarios: **steady** — interleaved single-image requests per
    network through one micro-batching Server (shared EngineCache),
    per-network throughput/latency + cache stats; **overload** — ~2x+
    capacity offered against a bounded queue, proving admission control
    sheds with typed ``Overloaded`` while accepted requests keep bounded
    latency; **sweep** — an offered-QPS ladder (fractions and multiples
    of measured capacity) recording p50/p95/p99 + shed rate per rung, so
    the gate holds the whole SLO curve. The CI gate
    (tools/compare_bench.py) holds the overload and sweep invariants
    against the committed baseline.
    """
    assert len(networks) >= 2, "serving bench covers >= 2 networks"
    steady = _serving_steady(networks, requests_per_net, max_batch,
                             window_ms)
    overload = _serving_overload(networks[0])
    sweep = _serving_sweep(networks[0])
    payload = {
        "kind": "serving",
        "networks": list(networks),
        "max_batch": max_batch,
        "window_ms": window_ms,
        "scenarios": {"steady": steady, "overload": overload,
                      "sweep": sweep},
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    rung_summary = ", ".join(
        f"{r['load_factor']:g}x: p95 {r['p95_s']:.3f}s shed "
        f"{r['shed_rate']:.2f}" for r in sweep["rungs"])
    print(f"wrote {path}: steady {steady['requests']} requests over "
          f"{len(networks)} networks in {steady['wall_s']:.2f}s "
          f"({steady['throughput_rps']:.1f} req/s, cache "
          f"{steady['cache']['misses']} builds / {steady['cache']['hits']} "
          f"hits); overload shed {overload['shed']}/{overload['offered']} "
          f"(rate {overload['shed_rate']:.2f}), accepted p95 "
          f"{overload['accepted_p95_s']:.3f}s <= {overload['p95_bound_s']}s "
          f"bound, {overload['unresolved']} unresolved; sweep "
          f"[{rung_summary}]")


def emit_streaming_json(path, *, networks=("resnet18", "mobilenet_v2"),
                        n_streams=4, fps=30.0, frames_per_stream=45,
                        classify_requests=8,
                        scenarios=(("steady", 0.008), ("overload", 0.050))):
    """Run the multi-stream deadline scenario and dump BENCH_streaming.json.

    Each scenario opens ``n_streams`` simulated-clock 30 fps sessions
    (round-robin over ``networks``, phase-staggered) on one shared-cache
    ``Server`` while a classify client pushes on-demand ``Server.submit``
    traffic through the same cache. The per-frame sim compute charge is
    the scenario knob: "steady" (charge < frame period) must hold a zero
    deadline-miss rate; "overload" (charge > period) must engage
    skip-to-latest and report the misses. Sim-time aggregates are
    deterministic — the CI gate compares them against the committed
    baseline — while classify latencies are wall-clock trend lines.
    """
    import threading

    import jax

    from repro.serving import Server, ServingOptions, StreamScheduler

    key = jax.random.key(0)
    imgs = [jax.random.normal(jax.random.fold_in(key, i), (32, 32, 3))
            for i in range(frames_per_stream)]
    period = 1.0 / fps
    out_scenarios = {}
    t_start = time.perf_counter()
    for name, charge_s in scenarios:
        server = Server(tiny=True, options=ServingOptions(
            max_batch=4, window_ms=5.0))
        for net in networks:  # build + jit outside the measured window
            server.run(net, imgs[0])
        streams = [server.open_stream(networks[i % len(networks)], fps=fps,
                                      sim_compute_s=charge_s,
                                      phase_s=i * period / n_streams,
                                      name=f"{name}-{i}")
                   for i in range(n_streams)]
        classify_lat = []

        def classify_client():
            for i in range(classify_requests):
                net = networks[i % len(networks)]
                t0 = time.perf_counter()
                server.run(net, imgs[i % len(imgs)], timeout=600)
                classify_lat.append(time.perf_counter() - t0)

        client = threading.Thread(target=classify_client)
        client.start()
        t0 = time.perf_counter()
        StreamScheduler(streams).run(frames_per_stream,
                                     lambda i, k: imgs[k])
        stream_wall = time.perf_counter() - t0
        client.join()
        per_stream = {s.name: s.stats() for s in streams}
        total = sum(st["frames"] for st in per_stream.values())
        misses = sum(st["deadline_misses"] for st in per_stream.values())
        dropped = sum(st["dropped"] for st in per_stream.values())
        lats = sorted(classify_lat)
        out_scenarios[name] = {
            "sim_compute_ms": charge_s * 1e3,
            "streams": per_stream,
            "aggregate": {
                "frames": total,
                "completed": total - dropped,
                "dropped": dropped,
                "drop_rate": dropped / total,
                "deadline_misses": misses,
                "deadline_miss_rate": misses / total,
            },
            # wall-clock (machine-dependent, never gated): how long the
            # real kernels took to chew through the simulated schedule,
            # and what the contending classify traffic saw
            "wall": {
                "stream_wall_s": stream_wall,
                "frames_per_wall_s": (total - dropped) / stream_wall,
                "classify_requests": len(lats),
                "classify_p50_s": lats[len(lats) // 2] if lats else None,
                "classify_p95_s": (lats[min(len(lats) - 1,
                                            round(0.95 * (len(lats) - 1)))]
                                   if lats else None),
            },
        }
        stats = server.stats()
        out_scenarios[name]["cache"] = stats["cache"]
        server.close()
    payload = {
        "kind": "streaming",
        "networks": list(networks),
        "n_streams": n_streams,
        "fps": fps,
        "frames_per_stream": frames_per_stream,
        "scenarios": out_scenarios,
        "wall_s": time.perf_counter() - t_start,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    for name, sc in out_scenarios.items():
        agg = sc["aggregate"]
        print(f"{name}: {agg['frames']} frames over {n_streams} streams, "
              f"miss rate {agg['deadline_miss_rate']:.3f}, "
              f"dropped {agg['dropped']}, classify p95 "
              f"{sc['wall']['classify_p95_s'] or float('nan'):.3f}s")
    print(f"wrote {path} in {payload['wall_s']:.1f}s")


def emit_quant_json(path, config="resnet18", n_images=8):
    """Accuracy-vs-speed across precisions (the BENCH_quant.json artifact).

    One fp32 reference engine of the tiny config supplies the parameter
    values and the ground-truth logits; each reduced-precision row reuses
    those same values (cast, or int8-quantized) so the sweep isolates
    precision from initialization. Rows:

      * ``float32`` — the reference (agreement 1.0 by construction);
      * ``bfloat16`` / ``float16`` — compute + storage at the reduced
        width, tuned under the dtype-keyed plan (byte terms halve, so
        ``est_time_s`` is the speed side of the trade);
      * ``int8`` — weight-only quantization via ``repro.quant``: int8
        codes + per-channel scales folded into the fused epilogue, fp32
        compute, fp32 plan reused. ``weight_bytes`` carries the ~4x
        storage saving; ``est_time_s`` stays the compute-dtype estimate.

    Everything is seeded, so rows are deterministic on a given platform —
    the CI gate compares agreement/xla-fallback against the committed
    baseline.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get, tiny_variant
    from repro.core import InferenceEngine
    from repro.core.dtypes import KERNEL_DTYPES, with_precision
    from repro.quant import quantization_error, quantize_params

    cfg = tiny_variant(get(config))
    ref = InferenceEngine(cfg)  # fp32 reference: params, plan, logits
    size = cfg.extra["img"]
    images = jax.random.normal(jax.random.key(0), (n_images, size, size, 3))
    ref_logits = np.asarray(ref.run_batch(images), np.float32)
    ref_top1 = ref_logits.argmax(-1)
    ref_max = np.abs(ref_logits).max() + 1e-12

    def cast_params(tree, dt):
        return jax.tree.map(
            lambda x: x.astype(dt)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    def param_bytes(tree):
        return int(sum(x.size * jnp.dtype(x.dtype).itemsize
                       for x in jax.tree.leaves(tree)))

    def row(name, eng, weight_bytes, extra=None):
        logits = np.asarray(eng.run_batch(images), np.float32)
        plan = eng.plan
        r = {
            "dtype": name,
            "n_images": n_images,
            "top1_agreement": float((logits.argmax(-1) == ref_top1).mean()),
            "logit_rel_err": float(np.abs(logits - ref_logits).max()
                                   / ref_max),
            "est_time_s": sum(c.est_time for c in plan.choices.values()),
            "est_bytes": sum(c.est_bytes for c in plan.choices.values()),
            "weight_bytes": weight_bytes,
            "xla_sites": sorted(n for n, c in plan.choices.items()
                                if c.algorithm == "xla"),
        }
        r.update(extra or {})
        return r

    rows = [row("float32", ref, param_bytes(ref.params))]
    for dt in KERNEL_DTYPES:
        if dt == "float32":
            continue
        cfg_v = with_precision(cfg, dt)
        eng = InferenceEngine(cfg_v, params=cast_params(ref.params, dt))
        rows.append(row(dt, eng, param_bytes(eng.params)))
    qparams, qreport = quantize_params(ref.params)
    qeng = InferenceEngine(cfg, params=qparams, plan=ref.plan)
    conv_w_fp32 = sum(q.codes.size * 4 for q in qreport.values())
    q_storage = sum(q.storage_bytes for q in qreport.values())
    werr = quantization_error(ref.params, qreport)
    rows.append(row(
        "int8", qeng, param_bytes(ref.params) - conv_w_fp32 + q_storage,
        {"quantized_sites": len(qreport),
         "max_weight_rounding_rel_err": max(werr.values())}))

    payload = {"kind": "quant", "config": cfg.name, "n_images": n_images,
               "rows": rows}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    for r in rows:
        print(f"{r['dtype']:>9}: top1 agreement {r['top1_agreement']:.3f}, "
              f"logit rel err {r['logit_rel_err']:.2e}, "
              f"est {r['est_time_s'] * 1e6:.1f}us, "
              f"weights {r['weight_bytes'] / 1e3:.1f}kB, "
              f"{len(r['xla_sites'])} xla sites")
    print(f"wrote {path}: {len(rows)} precision rows on {cfg.name}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH",
                    help="emit the per-layer plan + proxy timings as JSON "
                         "and exit (CI smoke mode)")
    ap.add_argument("--config", default="resnet18",
                    help="network for --json (tiny variant is used)")
    ap.add_argument("--serve", metavar="PATH",
                    help="run the micro-batched serving bench and emit "
                         "throughput/latency JSON (BENCH_serving.json)")
    ap.add_argument("--stream", metavar="PATH",
                    help="run the multi-stream deadline bench and emit "
                         "per-stream miss-rate JSON (BENCH_streaming.json)")
    ap.add_argument("--quant", metavar="PATH",
                    help="run the precision sweep (fp32/bf16/fp16/int8) and "
                         "emit the accuracy-vs-speed JSON (BENCH_quant.json)")
    args = ap.parse_args(argv)
    from repro import compile_cache

    compile_cache.enable()
    if args.json:
        emit_json(args.json, config=args.config)
        return
    if args.serve:
        emit_serving_json(args.serve)
        return
    if args.stream:
        emit_streaming_json(args.stream)
        return
    if args.quant:
        emit_quant_json(args.quant, config=args.config)
        return

    t0 = time.time()
    from benchmarks import conv_algorithms, conv_arith, conv_memory, roofline

    _section("paper Table 3: global-memory traffic (analytic vs measured)")
    conv_memory.main()

    _section("paper Fig. 5: algorithm x layer x device (roofline cost model)")
    conv_algorithms.main()

    _section("paper Table 4: arithmetic profile + kernel wall (interpret)")
    conv_arith.main()

    _section("autotuner choices per ResNet layer (paper's tuning library)")
    from repro.core import ConvSpec, select
    from repro.configs.resnet import PAPER_CONV_LAYERS

    print("layer,algorithm,est_us_v5e,est_bytes_MB,vmem_MB")
    for layer in PAPER_CONV_LAYERS:
        ch = select(ConvSpec(h=layer.h, w=layer.w, c=layer.c_in, k=layer.c_out))
        print(f"{layer.name},{ch.algorithm},{ch.est_time * 1e6:.2f},"
              f"{ch.est_bytes / 1e6:.2f},{ch.vmem / 2 ** 20:.2f}")

    _section("roofline (from dry-run artifacts)")
    roofline.main()

    print(f"\n# benchmarks done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
