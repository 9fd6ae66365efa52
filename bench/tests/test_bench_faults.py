"""Runs of tiny cells on the CPU with the timed path broken underneath,
and the calibration's control: each has to come out as not correct, or
read far from the program."""
import time

import pytest

import bench_tiny
from benchlib import harness


def run_tiny(workload, config, seconds=0.5, **kw):
    cell, pcfg = bench_tiny.tiny_cell(workload, config)
    return harness.run_cell(cell, 2**31 + 99, seconds, False,
                            time.perf_counter(), program_cfg=pcfg, **kw)


def _alter_one_answer(program):
    run = program.engine.run

    def altered(image):
        out = run(image)
        return out.at[3].add(0.2 * abs(out).max())
    program.engine.run = altered


def _swap_batch_answers(program):
    run_batch = program.engine.run_batch

    def swapped(images):
        return run_batch(images)[::-1]
    program.engine.run_batch = swapped


def _misslice_padded_batches(program):
    """Rows handed back in the wrong order, only in batches padded to a
    bucket (filler rows repeat the last image): set-up's ragged dispatches."""
    run_batch = program.engine.run_batch

    def missliced(images):
        out = run_batch(images)
        return out[::-1] if bool((images[-1] == images[-2]).all()) else out
    program.engine.run_batch = missliced


@pytest.mark.parametrize("workload,config,fault", [
    ("resnet18.b1", "resnet18-224-fp32", _alter_one_answer),
    ("mobilenet_v2.c16", "mobilenet_v2-224-fp32", _swap_batch_answers),
    ("resnet18.c16", "resnet18-224-fp32", _misslice_padded_batches),
])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        workload, config, fault):
    result, checks, _ = run_tiny(workload, config, on_program=fault)
    assert result["correct"] is False
    assert checks["gap_highest"]["value"] > checks["gap_highest"]["limit"]


def test_a_failed_request_is_not_correct():
    def fail(program):
        def broken(*a, **k):
            raise RuntimeError("injected")
        program.engine.run = broken
    result, checks, _ = run_tiny("resnet18.b1", "resnet18-224-fp32",
                                 on_program=fail)
    assert result["correct"] is False and result["failed"] > 0


@pytest.mark.parametrize("workload,config", [
    ("resnet18.b1", "resnet18-224-fp32"),
    ("mobilenet_v2.c16", "mobilenet_v2-224-fp32")])
def test_control_and_planted_fault_readings(workload, config):
    """The calibration's two other sides at a tiny size: the bf16 control,
    put in the program's place, comes out not correct by the
    configuration's limits, and so does a misrouted answer."""
    import calibrate

    cell, pcfg = bench_tiny.tiny_cell(workload, config)
    seed = 2**31 + 7
    result, checks, notes = harness.run_cell(
        cell, seed, 0.3, False, time.perf_counter(), program_cfg=pcfg)
    assert result["correct"] is True
    limits = cell.model.cfg["check"]
    control = calibrate.side_readings(
        cell, seed, calibrate.control_answers(cell, seed, notes["compared"]))
    assert control["bf16_exact_share"] == 1.0
    assert any(control[k] > limits[k] for k in limits)
    result, checks, _ = harness.run_cell(
        cell, seed, 0.3, False, time.perf_counter(), program_cfg=pcfg,
        on_program=calibrate.misroute)
    assert result["correct"] is False
    assert checks["gap_highest"]["value"] > checks["gap_highest"]["limit"]
