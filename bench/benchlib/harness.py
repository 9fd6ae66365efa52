"""One run of one cell: set-up, the measured window, the check, the metrics.

Everything that belongs to one configuration, one traffic mix or one metric
is found by its name in ``BENCHMARK.json``: ``bench/configs/<config>.json``
and ``.py``, ``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py``
(a ``read(ctx)`` that returns the metric's value, or None where it finds
nothing to read).
"""
from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from benchlib import refops, traffic
from benchlib import trace as trace_mod
from benchlib.model import Model, load_module

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent
METRIC_DIR = BENCH_DIR / "metrics"
TRACE_SECONDS = 4.0   # a traced run measures at most this long
WARM_TRAFFIC_S = 1.0  # the cell's own traffic, unmeasured, before the window
REF_BLOCK = 16        # images per reference call


class BenchError(Exception):
    """The run cannot be made: a name, a file or the device is wrong."""


# ---------------------------------------------------------------------------
# what BENCHMARK.json says about a cell

@dataclass
class Cell:
    name: str
    model: Model
    mix: dict
    chips: int
    metrics: dict = field(default_factory=dict)  # name -> BENCHMARK entry
    per_layer: dict = field(default_factory=dict)


def _applies(entry, cell_name):
    return "workloads" not in entry or cell_name in entry["workloads"]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name!r}: no config {w['config']!r}")
    try:
        model = Model(w["config"])
        mix = traffic.load(w["traffic"])
    except KeyError as e:
        raise BenchError(str(e)) from None
    cell = Cell(name, model, mix, int(w["chips"]))
    for kind, out in (("end_to_end", cell.metrics),
                      ("per_layer", cell.per_layer)):
        for m in bench[kind]:
            if _applies(m, name):
                out[m["name"]] = m
    for metric in (*cell.metrics, *cell.per_layer):
        reader(metric)  # an unknown metric fails before any run
    return cell


def reader(metric: str):
    """The module of ``bench/metrics/<metric>.py``."""
    path = METRIC_DIR / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"no reader for metric {metric!r} in {METRIC_DIR}")
    return load_module(path, "bench_metric_" + metric.replace(
        ".", "_").replace("-", "_"))


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# the system under test, as a client sees it

class Program:
    """A ``repro.serving.Server`` serving one network, built with the
    benchmark's weights. ``program_cfg`` replaces the registry's config of
    the network (the CPU tests serve a tiny variant)."""

    def __init__(self, model: Model, params, program_cfg=None):
        from repro.configs import get
        from repro.models.registry import cnn_module
        from repro.serving import Server, ServingOptions

        self.cfg = program_cfg if program_cfg is not None \
            else get(model.cfg["network"])
        self.network = model.cfg["network"] if program_cfg is None \
            else program_cfg
        theirs = _shapes(cnn_module(self.cfg).model_specs(self.cfg))
        if theirs != dict(model.param_shapes):
            diff = sorted(set(theirs.items()) ^ set(model.param_shapes.items()))
            raise BenchError(f"the served {model.name} takes other parameters "
                             f"than the layer table gives: {diff[:6]}")
        self.server = Server(options=ServingOptions(**model.cfg["serving"]))
        self.engine = self.server.engines.get(self.cfg, params=params)
        self.server.warm(self.network)
        self.max_batch = self.server.options.max_batch

    def submit(self, image):
        return self.server.submit(self.network, image)

    def stats(self) -> dict:
        nets = self.server.stats()["networks"]
        (only,) = nets.values()
        return only

    def close(self):
        self.server.close()


def _shapes(tree, path=()):
    if isinstance(tree, dict):
        out = {}
        for k in tree:
            out.update(_shapes(tree[k], path + (k,)))
        return out
    return {path: tuple(tree.shape)}


# ---------------------------------------------------------------------------
# counting compilations

@contextlib.contextmanager
def count_compiles():
    """Counts the traces and compilations made while the block runs:
    yields a list whose length is that count when the block ends."""
    import jax

    events = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")
    seen = []

    def listen(event, duration, **_):
        if event in events:
            seen.append(event)  # list.append is atomic under the GIL
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


# ---------------------------------------------------------------------------
# one run

@dataclass
class Context:
    """What a metric reader reads."""
    cell: Cell
    peaks: dict
    window: traffic.Window
    setup_s: float
    dispatches: dict          # real batch size -> dispatches in the window
    trace: trace_mod.Trace | None = None


def images_for(model: Model, mix: dict, seed: int) -> np.ndarray:
    """The cell's distinct images for ``seed``: random fields with the
    low-frequency structure of photographs (noise drawn on 7x7, 28x28 and
    112x112 grids, blown up to the image size, each coarser grid stronger)
    and a colour cast each, normalised to unit variance. White noise would
    give every image the same pooled features, and nearly the same logits,
    so a wrong answer could pass for a right one."""
    rng = np.random.default_rng([seed, 1])
    n = int(mix["images"])
    h, w, c = model.cfg["image"]
    out = np.zeros((n, h, w, c), np.float32)
    for cells, amp in ((7, 1.0), (28, 0.5), (112, 0.25)):
        grid = rng.standard_normal((n, cells, cells, c), dtype=np.float32)
        rows = np.arange(h) * cells // h
        cols = np.arange(w) * cells // w
        out += amp * grid[:, rows][:, :, cols]
    out += 0.5 * rng.standard_normal((n, 1, 1, c), dtype=np.float32)
    return out / out.std(axis=(1, 2, 3), keepdims=True)


def _dispatch_delta(before: dict, after: dict) -> dict:
    b, a = before["batch_histogram"], after["batch_histogram"]
    return {int(k): a[k] - b.get(k, 0) for k in a if a[k] - b.get(k, 0)}


def _profile_options():
    """The device's operations alone: no host or Python tracing (see
    ``benchlib.trace``)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    return opts


def _window_marks():
    """A context manager that runs ``trace.OPEN_MARK`` as the window opens
    and ``trace.CLOSE_MARK`` as it closes, both compiled here, outside it."""
    import jax
    import jax.numpy as jnp

    def bench_window_open(x):
        return x + 1

    def bench_window_close(x):
        return x - 1

    marks = [jax.jit(f) for f in (bench_window_open, bench_window_close)]
    x = jnp.zeros((8, 128), jnp.float32)
    for m in marks:
        m(x).block_until_ready()

    @contextlib.contextmanager
    def mark():
        marks[0](x).block_until_ready()
        yield
        marks[1](x).block_until_ready()
    return mark()


# each gap that ``compare`` can read, and the reference it reads against
GAPS = {"gap_highest": refops.REFERENCE, "gap_stated": refops.STATED}


def bf16_exact_share(requests) -> float:
    """The share of the answers' logits that are bfloat16 values: about
    1/65536 where they are stored in float32, 1 where in bfloat16."""
    import jax.numpy as jnp

    got = np.concatenate([np.asarray(r.answer, np.float32).ravel()
                          for r in requests])
    return float(np.mean(got.astype(jnp.bfloat16).astype(np.float32) == got))


def compare(model: Model, params, images, requests, names) -> dict:
    """The served answers against plain references, image by image: for each
    name of ``GAPS``, the largest, over answers, of max|served - reference|
    over the range (max - min) of the reference's logits, the reference
    computed at that name's precision. The range, and not the largest
    |logit|, so that an offset common to all classes, which no
    classification depends on, does not shrink the gap. With
    ``bf16_exact_share`` in ``names``, that share too."""
    import jax

    used = sorted({r.image for r in requests})
    out = {}
    for name in names:
        if name == "bf16_exact_share":
            out[name] = bf16_exact_share(requests)
            continue
        fwd = model.reference(GAPS[name])
        ref = {}
        for i in range(0, len(used), REF_BLOCK):
            block = used[i:i + REF_BLOCK]
            # one block shape, so the reference compiles once
            padded = block + block[-1:] * (REF_BLOCK - len(block))
            got = np.asarray(fwd(params, jax.device_put(images[padded])))
            ref.update(zip(block, got))
        gap = 0.0
        for r in requests:
            want = ref[r.image]
            got = np.asarray(r.answer, dtype=np.float32)
            if got.shape != want.shape or not np.isfinite(got).all():
                gap = 1e30
                break
            gap = max(gap, float(np.max(np.abs(got - want)))
                      / float(np.max(want) - np.min(want)))
        out[name] = gap
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, program_cfg=None, on_program=None):
    """Set up, measure, check. Returns (result dict, checks dict, notes).

    ``on_program(program)`` is called once the program is built, before
    its first dispatch (the tests break the timed path there)."""
    import jax

    model = cell.model
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else {}

    phases = {"start": time.perf_counter() - t_start}
    params = jax.block_until_ready(model.init_params(seed))
    images = images_for(model, cell.mix, seed)
    phases["weights_images"] = time.perf_counter() - t_start
    program = Program(model, params, program_cfg)
    phases["program"] = time.perf_counter() - t_start
    if on_program is not None:
        on_program(program)
    # every dispatch size the mix can form, twice: ragged sizes run padded
    # to a bucket; their answers are compared with the window's
    warm = []
    for n in traffic.batch_sizes(cell.mix, program.max_batch):
        for k in range(2):
            sent = [(n * k + i) % len(images) for i in range(n)]
            tickets = [program.submit(images[i]) for i in sent]
            for i, t in zip(sent, tickets):
                warm.append(traffic.Request(-1, i, 0.0))
                try:
                    warm[-1].answer = t.result(timeout=1200)
                except Exception as e:  # a failed answer is counted
                    warm[-1].error = e
    warm_dispatches = dict(program.stats()["batch_histogram"])
    phases["warm_shapes"] = time.perf_counter() - t_start
    traffic.run(lambda i: program.submit(images[i]), cell.mix,
                WARM_TRAFFIC_S, seed + 1)
    setup_s = time.perf_counter() - t_start

    window_s = min(seconds, TRACE_SECONDS) if trace else seconds
    # only a TPU's trace has a device plane to read
    profile = trace and dev.platform == "tpu"
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if profile else None
    mark = _window_marks() if profile else None
    before = program.stats()
    with count_compiles() as compiled:
        if profile:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
        try:
            window = traffic.run(lambda i: program.submit(images[i]),
                                 cell.mix, window_s, seed, mark=mark)
        finally:
            if profile:
                jax.profiler.stop_trace()
    compiles = len(compiled)
    after = program.stats()
    stats = [d.memory_stats() for d in jax.local_devices()]
    device["memory_peak_bytes"] = max(
        (s.get("peak_bytes_in_use", 0) for s in stats if s), default=0)

    tr = None
    if profile:
        try:
            tr = trace_mod.read_xplane(trace_mod.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_ns() / 1e9
        device["window_s"] = tr.window_ns / 1e9

    program.close()
    del program
    gc.collect()

    sent = window.sent()
    answered = [r for r in sent if r.error is None and r.answer is not None]
    failed = len(sent) - len(answered)
    warm_answered = [r for r in warm if r.error is None]
    compared = answered + warm_answered
    readings = compare(model, params, images, compared,
                       model.cfg["check"]) if answered else {}

    ctx = Context(cell, peaks, window, setup_s,
                  _dispatch_delta(before, after), tr)
    names = cell.per_layer if trace else cell.metrics
    metrics = {}
    for name, entry in names.items():
        value = reader(name).read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": entry["unit"]}

    checks = {"failed": {"value": failed + len(warm) - len(warm_answered),
                         "limit": 0},
              "compiles_in_window": {"value": compiles, "limit": 0}}
    for name, limit in model.cfg["check"].items():
        checks[name] = {"value": readings.get(name), "limit": limit}
    correct = bool(answered) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    result = {"correct": correct, "attempted": len(sent), "failed": failed,
              "metrics": metrics, "device": device}
    if tr is not None:
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_by_next(tr)}
    result["checks"] = checks
    notes = {"setup": phases, "dispatches": ctx.dispatches,
             "warm_dispatches": warm_dispatches,
             "window_s": window.seconds,
             "longest_stall_s": window.longest_stall(),
             "compared": compared}
    return result, checks, notes


def report(result, checks, notes):
    """Readings first, then each compared number beside its limit as the
    last lines on stderr, then the result line as the last line on stdout."""
    out, err = sys.stdout, sys.stderr
    shown = {k: v for k, v in notes.items()
             if k != "compared"}
    print(f"readings: {json.dumps(shown, default=str)}", file=err)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
