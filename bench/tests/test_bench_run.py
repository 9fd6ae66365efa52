"""Whole runs of tiny cells on the CPU: everything a chip run does but the
look for the chip."""
import json
import os
import shutil
import subprocess
import sys
import time

import bench_tiny
from benchlib import harness

RUN = os.path.join(bench_tiny.BENCH, "run.py")


def run_tiny(workload, config, seconds=0.5, trace=False, **kw):
    cell, pcfg = bench_tiny.tiny_cell(workload, config)
    return harness.run_cell(cell, 2**31 + 99, seconds, trace,
                            time.perf_counter(), program_cfg=pcfg, **kw)


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_without_a_tpu_run_exits_nonzero_and_prints_no_result():
    p = subprocess.run([sys.executable, RUN, "--workload", "resnet18.b1",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=_cpu_env(),
                       cwd=bench_tiny.ROOT, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_alone_without_the_program_it_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "resnet18.b1", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=_cpu_env(), cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_unknown_workload_exits_nonzero():
    p = subprocess.run([sys.executable, RUN, "--workload", "nope.b1",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=_cpu_env(),
                       cwd=bench_tiny.ROOT, timeout=300)
    assert p.returncode != 0 and "nope.b1" in p.stderr


def test_a_tiny_run_reports_every_key_and_is_correct(capsys):
    result, checks, notes = run_tiny("resnet18.b1", "resnet18-224-fp32")
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                      "throughput_img_s", "setup_s"}
    assert list(result)[-1] == "checks"
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["gap_highest"]["value"] < 1e-4  # float32 on the CPU
    harness.report(result, checks, notes)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert err.strip().splitlines()[-1].startswith("check bf16_exact_share")


def test_a_traced_tiny_run_on_the_cpu_reports_what_it_can():
    result, _, _ = run_tiny("mobilenet_v2.c16", "mobilenet_v2-224-fp32",
                            trace=True)
    assert result["correct"] is True
    # the CPU has no device trace: only the dispatch log's reader reads
    assert set(result["metrics"]) == {"batch_mean.c16"}
    assert "breakdown" not in result


def test_reference_agrees_with_the_pallas_kernels_in_interpret_mode(
        monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_use_pallas", lambda impl: impl != "jnp")
    for workload, config in (("resnet18.b1", "resnet18-224-fp32"),
                             ("mobilenet_v2.b1", "mobilenet_v2-224-fp32")):
        result, checks, notes = run_tiny(workload, config, seconds=0.2)
        assert result["attempted"] > 0 and result["correct"] is True
        assert checks["gap_highest"]["value"] < 1e-4
