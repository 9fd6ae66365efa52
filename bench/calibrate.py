"""Readings that the limits of the check are set from; not run by the
benchmark's own runs.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 2

For each seed, in one process: one run of the cell as ``run.py`` makes it,
at the cell's own sizes and load for ``--seconds``, and every number that
``harness.compare`` can read of its answers; then the control, the plain
reference in bfloat16 storage (``refops.CONTROL``) put in the program's
place, and the program's own bfloat16 path on the same weights, each
answering the same requests, read by the same comparison and judged by the
configuration's limits. With ``--fault misroute`` the program runs
with a fault planted in its timed path instead: each answer is another
request's. Prints one JSON line per seed and side.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def control_answers(cell, seed, compared):
    """The requests of ``compared`` answered by the control."""
    import jax

    from benchlib import harness, refops
    from benchlib.traffic import Request

    model = cell.model
    params = model.init_params(seed)
    images = harness.images_for(model, cell.mix, seed)
    fwd = model.reference(refops.CONTROL)
    answers = {}
    used = sorted({r.image for r in compared})
    for i in range(0, len(used), harness.REF_BLOCK):
        block = used[i:i + harness.REF_BLOCK]
        padded = block + block[-1:] * (harness.REF_BLOCK - len(block))
        out = jax.device_get(fwd(params, jax.device_put(images[padded])))
        answers.update(zip(block, out))
    return [Request(0, r.image, 0.0, answer=answers[r.image])
            for r in compared]


def program_bf16_answers(cell, seed, compared, program_cfg=None):
    """The requests of ``compared`` answered by the program's own bfloat16
    path (``repro.core.dtypes.with_precision``), on the same weights cast to
    bfloat16, one at a time."""
    import jax
    import jax.numpy as jnp

    from benchlib import harness
    from benchlib.traffic import Request
    from repro.configs import get
    from repro.core.dtypes import with_precision

    model = cell.model
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          model.init_params(seed))
    images = harness.images_for(model, cell.mix, seed)
    cfg = with_precision(program_cfg if program_cfg is not None
                         else get(model.cfg["network"]), "bfloat16")
    program = harness.Program(model, params, cfg)
    try:
        answers = {}
        for i in sorted({r.image for r in compared}):
            answers[i] = program.submit(images[i]).result(timeout=1200)
    finally:
        program.close()
    return [Request(0, r.image, 0.0, answer=answers[r.image])
            for r in compared]


def side_readings(cell, seed, requests):
    """Every number ``harness.compare`` can read."""
    from benchlib import harness

    model = cell.model
    return harness.compare(model, model.init_params(seed),
                           harness.images_for(model, cell.mix, seed),
                           requests, [*harness.GAPS, "bf16_exact_share"])


def misroute(program):
    """Plant a fault where answers are produced: a batch-1 dispatch answers
    the image of the dispatch before it, a batch its rows rotated by one."""
    engine = program.engine
    run, run_batch = engine.run, engine.run_batch
    previous = []

    def run_misrouted(image):
        previous.append(image)
        return run(previous[-2] if len(previous) > 1 else image)

    def run_batch_misrouted(images):
        import jax.numpy as jnp

        return jnp.roll(run_batch(images), 1, axis=0)
    engine.run, engine.run_batch = run_misrouted, run_batch_misrouted


def readings(cell, seed, seconds, fault=None):
    """[program's line, the controls' lines] for one seed; with ``fault``,
    the faulty program's line alone."""
    from benchlib import harness

    result, checks, notes = harness.run_cell(
        cell, seed, seconds, False, time.perf_counter(),
        on_program={"misroute": misroute}[fault] if fault else None)
    limits = cell.model.cfg["check"]
    compared = notes["compared"]
    program = {"workload": cell.name, "seed": seed,
               "side": f"fault:{fault}" if fault else "program",
               "readings": side_readings(cell, seed, compared),
               "correct": result["correct"],
               "attempted": result["attempted"],
               "compared": len(compared),
               "warm_dispatches": notes["warm_dispatches"]}
    if fault:
        return [program]
    lines = [program]
    for side, answer in (("control", control_answers),
                         ("control:program_bf16", program_bf16_answers)):
        line = {"workload": cell.name, "seed": seed, "side": side}
        try:
            got = side_readings(cell, seed, answer(cell, seed, compared))
        except Exception as e:  # a control that crashes has failed
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        else:
            line["readings"] = got
            line["fails"] = [k for k in limits if got[k] > limits[k]]
        lines.append(line)
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--fault", choices=("misroute",))
    args = ap.parse_args(argv)

    import jax

    from benchlib import harness
    from run import use_checkout_cache

    use_checkout_cache(jax)
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in readings(cell, seed, args.seconds, args.fault):
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
