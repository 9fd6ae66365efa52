"""The reduction from trace events to metrics, on a small synthetic
trace whose answers are worked out by hand (times in ns)."""
import pytest

import bench_tiny  # noqa: F401  (paths)
from benchlib import trace as T


def synthetic():
    # marks end at 0 and start at 100: the window is [0, 100). Two runs of
    # the forward "fwd" and one of a small "copy" program, ops inside them
    # (two Pallas kernels), one op running past the window.
    ops = [T.Event(-5, 0, "add"),                 # inside the open mark
           T.Event(10, 20, "conv", pallas=True),
           T.Event(15, 30, "fusion"),             # overlaps the first
           T.Event(50, 60, "conv", pallas=True),
           T.Event(60, 65, "copy"),
           T.Event(95, 110, "fusion")]
    # a program's run starts a little before its first op
    modules = [T.Event(-5, 0, T.OPEN_MARK), T.Event(9, 30, "fwd"),
               T.Event(49, 60, "fwd"), T.Event(60, 65, "copy"),
               T.Event(95, 110, "fwd"), T.Event(110, 112, "fwd"),
               T.Event(100, 101, T.CLOSE_MARK)]
    return T.window_between_marks(ops, modules)


def test_window_between_the_marks():
    tr = synthetic()
    assert tr.window == (0, 100)
    assert all(m.name in ("fwd", "copy") for m in tr.modules)
    with pytest.raises(ValueError):
        T.window_between_marks([], [T.Event(0, 1, T.OPEN_MARK)])


def test_busy_union_and_idle_share():
    tr = synthetic()
    assert tr.busy() == [(10, 30), (50, 65), (95, 100)]
    assert tr.busy_ns() == 40
    assert tr.idle_share() == pytest.approx(0.6)
    assert tr.pallas_ns() == 20


def test_gaps_between_busy_intervals():
    assert T.gaps([(10, 30), (50, 65), (95, 100)], 0, 100) == [
        (0, 10), (30, 50), (65, 95)]
    assert T.gaps([], 0, 5) == [(0, 5)]
    assert T.gaps([(0, 5)], 0, 5) == []


def test_forward_runs_and_their_gaps():
    tr = synthetic()
    runs = tr.forward_runs()
    assert [(r.start, r.end) for r in runs] == [(9, 30), (49, 60),
                                                (95, 110)]
    assert tr.forward_gaps_ns() == [19, 35]
    assert T.program_name("jit__unknown(10682643911324920409)") == "_unknown"


def test_top_ops_clipped_to_the_window():
    assert T.top_ops(synthetic(), n=2) == [["conv", 20e-9],
                                           ["fusion", 20e-9]]
    assert dict(T.top_ops(synthetic()))["copy"] == pytest.approx(5e-9)


def test_idle_gaps_by_the_program_that_ends_them():
    out = dict(T.idle_by_next(synthetic()))
    # (0, 10) and (30, 50) end as "fwd" starts; (65, 95) as "fwd" starts
    assert out == {"before fwd": pytest.approx(60e-9)}
    tr = T.Trace((0, 10), [T.Event(0, 4, "a")], [T.Event(0, 4, "p")])
    assert T.idle_by_next(tr) == [["window end", 6e-9]]


def test_op_names_from_the_chip_trace():
    pallas = ('%ilpm_conv.17 = f32[1,7,7,512]{3,2,1,0:T(8,128)S(1)} '
              'custom-call(f32[1,1,9,9,512]{4,3,2,1,0} %pad_bitcast_fusion.3),'
              ' custom_call_target="tpu_custom_call", operand_layout_'
              'constraints={f32[1,1,9,9,512]{4,3,2,1,0}}')
    other = ('%custom-call.14 = f32[3,3,128,256]{3,2,1,0} custom-call(f32[1,'
             '3,128,256]{3,2,1,0} %slice-done.25), '
             'custom_call_target="ConcatBitcast"')
    assert T.op_family(pallas) == "ilpm_conv" and T._is_pallas(pallas)
    assert T.op_family(other) == "custom-call" and not T._is_pallas(other)
    assert T.op_family("%while.3 = (s32[]) while(...)") == "while"


def test_a_window_recorded_on_the_chip():
    """Device events of a 0.02 s traced window of resnet18.b1 on a TPU v5e:
    four runs of the forward, ~4 ms apart, the chip busy ~4% of the time,
    a little over half of that in Pallas kernels."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "resnet18_b1_trace.json")
    fix = json.load(open(path))
    ops = [T.Event(s, e, name, pallas) for s, e, name, pallas in fix["ops"]]
    modules = [T.Event(s, e, T.program_name(n)) for s, e, n in fix["modules"]]
    tr = T.window_between_marks(ops, modules)
    want = fix["expected"]
    assert tr.window_ns == want["window_ns"]
    assert tr.busy_ns() == want["busy_ns"]
    assert tr.pallas_ns() == want["pallas_ns"]
    assert len(tr.forward_runs()) == want["forward_runs"] == 4
    assert tr.forward_gaps_ns() == want["forward_gaps_ns"]
    assert all(3e6 < g < 6e6 for g in tr.forward_gaps_ns())
    assert 0.02 < tr.busy_ns() / tr.window_ns < 0.06
    assert 0.5 < tr.pallas_ns() / tr.busy_ns() < 0.6
    assert T.top_ops(tr, 3) == want["top_ops"]
    assert T.idle_by_next(tr) == want["idle_gaps"]
    assert T.idle_by_next(tr)[0][0] == "before _unknown"  # the forward
    for name in fix["op_name_samples"]:
        family = T.op_family(name)
        assert family and " " not in family
        assert T._is_pallas(name) == ("tpu_custom_call" in name)
