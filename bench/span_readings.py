"""Readings of the program's own spans on the device trace's clock; not
run by the benchmark's own runs. Needs a TPU: elsewhere it exits with 2
and prints nothing.

    python bench/span_readings.py --workload <name>[,<name>...] \\
        --seeds 1,2 --seconds 4 --record 1[,0]

For each workload, seed and ``--record`` value, in one process: the
cell's set-up as ``run.py`` makes it (weights and images from the seed,
the program, every dispatch size the mix forms warmed twice, a second of
the cell's traffic), with the program's span recorder
(``repro.serving.spans``) on from the start when recording; then a window
of ``--seconds`` (at most 4) under a device-only profiler trace, its ends
marked on the device and, when recording, on the host. Prints one JSON
line per run: the window's p50 and throughput (host clock), the device's
idle share and programs run, and when recording the span readings
(``hostspans``), ``span_clock_error_us``, ``span_clock_shift_us`` and
``idle_by_span``.

This copies ``harness.run_cell``'s set-up and traced window; it goes once
``run_cell`` records the spans itself.
"""
import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


class _Held:
    """A ticket that stamps when its client holds the answer."""

    def __init__(self, ticket, held):
        self._ticket, self._held = ticket, held

    def result(self, timeout=None):
        out = self._ticket.result(timeout)
        self._held[self._ticket.id] = time.perf_counter_ns()
        return out

    @property
    def done_at(self):
        return self._ticket.done_at


def readings(cell, seed, seconds, record, program_cfg=None):
    """One run's JSON line as a dict; where there is no TPU (the CPU
    tests), without the device's numbers."""
    import jax

    from benchlib import harness, hostspans, stats, traffic
    from benchlib import trace as trace_mod
    from repro.serving import spans

    rec = spans.start() if record else None
    model = cell.model
    dev = jax.devices()[0]
    profile = dev.platform == "tpu"
    held = {}
    try:
        params = jax.block_until_ready(model.init_params(seed))
        images = harness.images_for(model, cell.mix, seed)
        program = harness.Program(model, params, program_cfg)
        try:
            for n in traffic.batch_sizes(cell.mix, program.max_batch):
                for k in range(2):
                    sent = [(n * k + i) % len(images) for i in range(n)]
                    for t in [program.submit(images[i]) for i in sent]:
                        t.result(timeout=1200)

            def submit(i):
                return _Held(program.submit(images[i]), held)
            traffic.run(submit, cell.mix, harness.WARM_TRAFFIC_S, seed + 1)

            window_s = min(seconds, harness.TRACE_SECONDS)
            inner = harness._window_marks() if profile \
                else contextlib.nullcontext()

            @contextlib.contextmanager
            def marked():
                with inner:
                    if rec is not None:
                        rec.mark(hostspans.OPEN)
                    yield
                    if rec is not None:
                        rec.mark(hostspans.CLOSE)
            trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
            if profile:
                jax.profiler.start_trace(
                    trace_dir, profiler_options=harness._profile_options())
            try:
                window = traffic.run(submit, cell.mix, window_s, seed,
                                     mark=marked())
            finally:
                if profile:
                    jax.profiler.stop_trace()
        finally:
            program.close()
    finally:
        got = rec.stop() if rec is not None else []

    out = {"workload": cell.name, "seed": seed, "record": record,
           "device": dev.device_kind,
           "latency_p50_ms": stats.latency_percentile(window, 50),
           "throughput_img_s": window.completed_in_window() / window.seconds}
    tr = None
    if profile:
        try:
            path = trace_mod.find_xplane(trace_dir)
            tr = trace_mod.read_xplane(path)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = tr.window
        out["device_idle"] = 100.0 * tr.idle_share()
        out["device_programs"] = sum(1 for m in tr.modules
                                     if lo <= m.start < hi)
        runs = tr.forward_runs()
        out["forward_program"] = runs[0].name if runs else None
        out["idle_gaps"] = trace_mod.idle_by_next(tr)
    else:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if rec is None:
        return out
    marks = rec.marks
    out["batcher_wait_ms"] = hostspans.batcher_wait_ms(got, marks)
    out["handoff_ms"] = hostspans.handoff_ms(got, marks, held)
    out["host_dispatch_ms"] = hostspans.host_dispatch_ms(got, marks)
    out["engine_build_s"] = hostspans.engine_build_s(got, marks)
    out["dispatch_counters"] = hostspans.dispatch_counters(got, marks)
    out["span_medians_ms"] = hostspans.span_medians_ms(got, marks)
    if tr is not None:
        on_device, err, shift = hostspans.to_device_clock(got, marks, tr)
        out["span_clock_error_us"] = err
        out["span_clock_shift_us"] = shift
        out["idle_by_span"] = hostspans.idle_by_span(tr, on_device)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--record", default="1",
                    help="1 records spans, 0 does not; 1,0 runs each seed "
                         "both ways, in that order")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"span_readings: needs a TPU, found "
              f"{jax.devices()[0].platform}", file=sys.stderr)
        sys.exit(2)

    from benchlib import harness
    from run import use_checkout_cache

    use_checkout_cache(jax)
    for name in args.workload.split(","):
        cell = harness.load_cell(name)
        for seed in (int(s) for s in args.seeds.split(",")):
            for record in args.record.split(","):
                print(json.dumps(readings(cell, seed, args.seconds,
                                          record == "1")), flush=True)


if __name__ == "__main__":
    main()
