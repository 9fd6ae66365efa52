"""Single-image CNN inference engine — the paper's deployment scenario.

Wraps a CNN (ResNet or a MobileNet-style net) with the paper's
tune-once/run-many flow (§2.3):

  1. the model module's ``conv_specs`` enumerates the ConvSpec of every
     conv site in the network — for ResNet the 7x7/2 stem, every 3x3
     (strided stage entries included) and every 1x1 (bottleneck
     reduce/expand, projection shortcuts); for MobileNet the stem plus
     every depthwise and pointwise site, strided depthwise included;
  2. the autotuner turns that list into a ``TuningPlan`` (cost-model or
     measured mode) mapping each layer name to its tuned Choice —
     algorithm plus kernel parameters — costed as the fused conv+BN+act
     variant the forwards actually dispatch;
  3. the plan is threaded into the model's ``forward`` and jitted, so the
     compiled forward dispatches each layer to its own tuned kernel with
     its folded-BN/activation epilogue fused into the kernel; Winograd
     sites get their filter transform ``U = G g Gᵀ`` computed once here
     and cached for every subsequent forward;
  4. plans serialize to JSON (``save_plan`` / ``TuningPlan.load``) so a
     device tunes once offline and deployments just load the plan.

The per-layer traffic/FLOP report doubles as the energy proxy (paper §2.2:
off-chip traffic dominates edge energy).
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core.autotune import TuningPlan
from repro.core.convspec import ConvSpec
from repro.models.registry import cnn_module
from repro.models.spec import init_params


@dataclass
class LayerReport:
    name: str
    spec: ConvSpec
    algorithm: str
    est_time: float
    est_bytes: int
    est_flops: int
    params: tuple = ()


class InferenceEngine:
    """Tune-once, run-many single-image inference.

    ``algorithm="auto"`` tunes a per-layer plan (``tune_mode`` picks
    cost-model vs measured); a concrete algorithm name forces every 3x3
    conv onto that algorithm; ``plan=`` (a TuningPlan or a JSON path)
    skips tuning and deploys a saved plan.
    """

    def __init__(self, cfg, params=None, seed=0, algorithm="auto",
                 plan=None, tune_mode="cost_model"):
        assert cfg.family == "cnn"
        self.cfg = cfg
        self._model = cnn_module(cfg)
        self.params = params if params is not None else init_params(
            self._model.model_specs(cfg), seed, cfg.param_dtype)
        self.algorithm = algorithm
        if plan is not None and not isinstance(plan, TuningPlan):
            plan = TuningPlan.load(plan)  # a path: tune-once/deploy-many
        if plan is not None:
            self._validate_plan(plan)
        elif algorithm == "auto":
            plan = self.tune(mode=tune_mode)
        self.plan = plan
        self.reports = self._reports_from_plan(plan) if plan else []
        # Winograd filter transforms U = G g G^T are constant at inference
        # (weights frozen): compute each winograd site's U once now, not
        # per forward, and thread the cache into the jitted forward.
        self.winograd_u = self._winograd_cache(plan) if plan else {}
        # winograd_u rides as a jit *argument* (a pytree, like params),
        # not a closure constant: baked-in constants would be re-embedded
        # into every trace of every entry point below.
        # The forward consumes ONE name->Choice dict: per-conv choices plus
        # the tuner's block-level fusion decisions (`<block>.block` keys are
        # disjoint from conv-site keys). At fused sites the forward
        # dispatches the block megakernel and skips the constituent convs'
        # entries entirely.
        fwd1 = functools.partial(
            self._model.forward, cfg=cfg, algorithm=algorithm,
            plan={**plan.choices, **plan.block_choices}
            if plan is not None else None)
        self._fwd = jax.jit(fwd1)
        # Batch-dim-tolerant entry for the serving layer: map the *exact*
        # single-image computation over the batch inside one jitted call
        # (lax.map), so a micro-batched dispatch is bitwise-equal to N
        # sequential `run` calls — batching changes scheduling, never
        # numerics. One retrace per distinct B; serving pads batches to
        # power-of-two buckets to bound the trace count.
        self._fwd_batch = jax.jit(
            lambda params, images, winograd_u=None: jax.lax.map(
                lambda im: fwd1(params, images=im[None],
                                winograd_u=winograd_u)[0], images))
        # Streaming entry: the same single-image computation as `run`,
        # jitted with the frame buffer DONATED. A StreamSession
        # device_puts frame t+1 into a fresh slot while frame t computes
        # (double-buffering), and donation lets XLA reuse frame t's input
        # buffer instead of allocating per frame. On backends where no
        # output can alias the frame (CPU; logits are far smaller than
        # the image) XLA declines the donation with a UserWarning —
        # benign, so it's filtered rather than spamming every stream.
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        self._fwd_stream = jax.jit(fwd1, donate_argnames=("images",))

    # ------------------------------------------------------------------
    # plan construction

    def _conv_specs(self):
        """(name, ConvSpec) per planned conv site, keyed like the params.

        Delegated to the model module (``resnet.conv_specs`` /
        ``mobilenet.conv_specs``), which walks the exact geometry of its
        ``forward``.
        """
        return self._model.conv_specs(self.cfg)

    def _block_specs(self):
        """(name, FusedBlockSpec) per fusible block site, or () for model
        families without a block enumeration — block tuning is opt-in per
        model module, and a model that never grows one simply keeps
        per-layer plans."""
        fn = getattr(self._model, "block_specs", None)
        return fn(self.cfg) if fn is not None else ()

    def tune(self, mode="cost_model", **tune_kwargs) -> TuningPlan:
        """Build the per-layer TuningPlan (the offline step of §2.3).

        ``tune_kwargs`` reach the tuner: ``repeats`` and ``noise_floor``
        for measured mode (on real hardware use ``noise_floor=0`` for
        pure wall-clock selection). Sites are costed as their fused
        conv+BN+act variants (``epilogue=True``) because that is what the
        model forwards dispatch. Block sites (the model's ``block_specs``
        enumeration) tune alongside: sites where a fused megakernel beats
        the per-layer baseline get ``block_choices`` entries.
        """
        return autotune.build_plan(self._conv_specs(), mode=mode,
                                   epilogue=True,
                                   block_specs=self._block_specs(),
                                   **tune_kwargs)

    def _site_params(self, name: str):
        """Resolve a plan layer name ('s0b1.c2') to its param subtree."""
        p = self.params
        for part in name.split("."):
            p = p[part]
        return p

    def _winograd_cache(self, plan: TuningPlan) -> dict:
        """U = G g G^T per winograd-planned site, computed once per build
        (the paper's §5.2 'filter transform is free at inference')."""
        from repro.kernels import ref as _ref

        cache = {}
        for name, ch in plan.choices.items():
            if ch.algorithm != "winograd":
                continue
            try:
                w = self._site_params(name)["w"]
            except (KeyError, TypeError):
                continue  # plan site not in this param tree: skip
            # the transform einsums against fp32 G matrices (promoting the
            # result); cast back so a bf16/fp16 engine streams U at the
            # engine's element width, matching the cost model's accounting
            cache[name] = _ref.winograd_filter_transform(w).astype(w.dtype)
        return cache

    def _validate_plan(self, plan: TuningPlan) -> None:
        """A deployed plan must match this network's conv geometry *and*
        precision — ConvSpec carries ``dtype``, so a plan tuned in fp32
        cannot be deployed onto a bf16 engine (byte traffic, and therefore
        the tuned choices, differ)."""
        import logging

        ours = dict(self._conv_specs())
        mismatched = {n for n, spec in plan.specs.items()
                      if n in ours and ours[n] != spec}
        if mismatched:
            raise ValueError(
                f"tuning plan was built for a different network/input "
                f"size/dtype (engine dtype {self.cfg.dtype!r}); "
                f"mismatched specs for {sorted(mismatched)}")
        missing = ours.keys() - plan.specs.keys()
        extra = plan.specs.keys() - ours.keys()
        if missing or extra:
            logging.getLogger(__name__).warning(
                "tuning plan coverage mismatch: missing=%s (these layers "
                "fall back to untuned dispatch) extra=%s (ignored)",
                sorted(missing), sorted(extra))
        # Block sites: intersection-only (a plan with no/fewer fused sites
        # just runs per-layer there — fusion is an optimization, never a
        # coverage obligation), but a present block entry must match this
        # network's geometry AND dtype exactly, same contract as convs.
        our_blocks = dict(self._block_specs())
        bad_blocks = {n for n, bspec in plan.block_specs.items()
                      if n in our_blocks and our_blocks[n] != bspec}
        if bad_blocks:
            raise ValueError(
                f"tuning plan was built for a different network/input "
                f"size/dtype (engine dtype {self.cfg.dtype!r}); "
                f"mismatched block specs for {sorted(bad_blocks)}")

    def save_plan(self, path) -> None:
        assert self.plan is not None, "engine has no plan to save"
        self.plan.save(path)

    @staticmethod
    def _reports_from_plan(plan: TuningPlan):
        return [LayerReport(name, plan.specs[name], ch.algorithm,
                            ch.est_time, ch.est_bytes, ch.est_flops,
                            ch.params)
                for name, ch in plan.choices.items()]

    # ------------------------------------------------------------------

    def run(self, image):
        """image: (H, W, 3) single image -> logits (classes,)."""
        return self._fwd(self.params, images=image[None],
                         winograd_u=self.winograd_u or None)[0]

    def run_batch(self, images):
        """images: (B, H, W, 3) micro-batch -> logits (B, classes).

        Each element runs the identical batch-1 computation `run`
        dispatches (same tuned per-layer kernels, same epilogues), mapped
        inside one jitted call — outputs are bitwise-equal to sequential
        `run` calls. This is the serving layer's dispatch entry.
        """
        return self._fwd_batch(self.params, images,
                               winograd_u=self.winograd_u or None)

    def device_put_frame(self, image):
        """Start the async host→device transfer of one streaming frame;
        returns the (1, H, W, C) device buffer for ``run_stream``.

        Called at frame *arrival* (on the producer thread), so the
        transfer overlaps the in-flight frame's compute — the streaming
        double-buffer. ``image`` is (H, W, C) or already (1, H, W, C).
        """
        if getattr(image, "ndim", 3) == 3:
            image = image[None]
        return jax.device_put(image)

    def run_stream(self, frames):
        """One streaming frame -> logits (classes,).

        ``frames`` is the (1, H, W, C) device buffer from
        ``device_put_frame``; it is **donated** — dead after this call —
        so callers must hand in a fresh buffer per frame (the session's
        double-buffered slots do). Numerics are identical to ``run``:
        same forward, same tuned per-layer plan, same epilogues.
        """
        return self._fwd_stream(self.params, images=frames,
                                winograd_u=self.winograd_u or None)[0]

    def trace_count(self):
        """Number of distinct shapes the batch forward has been traced
        for — the serving tests use it to prove padded buckets bound
        retraces."""
        return self._fwd_batch._cache_size()

    def traffic_report(self):
        """Per-layer bytes/flops for every planned conv site — the energy
        proxy (DESIGN.md §7.5). Coverage follows the model module's
        ``conv_specs``: every backbone conv site (stem, strided entries,
        1x1s, depthwise/pointwise) has an entry."""
        return self.reports
