"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout, on a machine whose JAX finds at least
as many TPU chips as the cell asks for; it exits non-zero and prints no
result anywhere else. It makes the weights and images from ``--seed``,
serves the cell's traffic through ``repro.serving.Server`` for ``--seconds``
(at most 4 s when traced), checks every answer of the window against the
configuration's plain reference, and prints one JSON object as the last
line of its standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``, each compared number beside its limit. Those numbers are also
the last lines of its standard error.

JAX's compilation cache is kept in ``.jax_cache`` at the checkout's root,
so only the first run of a cell in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_checkout_cache(jax):
    """Compile cache at a fixed path in the checkout, whatever the
    environment says; every program is cached, however small."""
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None):
    args = parse(argv)
    from benchlib import harness

    try:
        cell = harness.load_cell(args.workload)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax

    use_checkout_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    try:
        result, checks, notes = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.report(result, checks, notes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
