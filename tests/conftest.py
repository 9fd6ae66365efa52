import inspect
import os
import sys
from pathlib import Path

# NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 device;
# only launch/dryrun.py forces the 512-placeholder-device topology.
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


# dispatch kwargs that are call-site geometry / fused-epilogue operands,
# not tuned kernel parameters — the spies drop them so recorded calls
# compare cleanly against plan Choice.params. The block-level keys ride
# along: residual/out_act (inverted residual geometry) and res (the
# shortcut operand — a tensor, not a tunable); so do the squeeze-and-
# excitation operands pool/gate.
NON_TUNED_KEYS = ("stride", "scale", "bias", "act", "u",
                  "residual", "res", "out_act", "pool", "gate")


def spy_algorithms(monkeypatch):
    """Wrap every registered kernel — per-conv AND block-level — and
    record (algorithm, tuned_params).

    Shared by the plan-dispatch tests: the spy wrappers take ``**params``
    (VAR_KEYWORD), so ``ops.kernel_params`` / ``ops.block_kernel_params``
    pass dispatch's kwargs through untouched; the recorded params are what
    dispatch was called with minus the non-tuned keys (stride/epilogue
    operands/the residual tensor). Block dispatches record under their
    block-algorithm names ("fused_inverted_residual" /
    "fused_residual_conv"), so e2e tests can assert a fused site produced
    exactly ONE dispatch where the per-layer plan produced two or three.
    """
    from repro.kernels import ops

    calls = []
    for table in (ops.ALGORITHMS, ops.BLOCK_ALGORITHMS):
        for name, fn in dict(table).items():
            def wrapper(x, w, *, impl="auto", _name=name, _fn=fn, **params):
                calls.append((_name, tuple(sorted(
                    (k, v) for k, v in params.items()
                    if k not in NON_TUNED_KEYS))))
                # re-apply the per-algorithm kwarg filter against the
                # *real* wrapper: the spy's **params signature disables
                # dispatch's own filtering, and the real kernels don't all
                # take every geometry key (e.g. im2col has no stride)
                accepted = inspect.signature(_fn).parameters
                return _fn(x, w, impl=impl,
                           **{k: v for k, v in params.items()
                              if k in accepted})
            monkeypatch.setitem(table, name, wrapper)
    return calls
