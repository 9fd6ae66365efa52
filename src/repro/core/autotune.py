"""Auto-tuning library — the paper implements one for its OpenCL kernels
(§5: "we also implemented an auto-tuning library to choose the optimal
combination of the kernel parameters"); this is its TPU analogue.

Two modes:
  * cost-model (default): per-algorithm HBM-traffic + FLOP + VMEM model on
    v5e constants; picks the feasible candidate with the lowest roofline
    time max(t_compute, t_memory). Runs at trace time, no hardware needed.
  * measured: times candidates (CPU interpret mode here, real TPU wall-clock
    in production) and picks the fastest — the paper's actual procedure.

The unit of output is the **TuningPlan**: a serializable map from layer name
to (ConvSpec, Choice) covering every conv site of a network. The engine
builds one plan per network (tune once — the paper's §2.3 argument that
single-image inference amortizes per-shape tuning), saves it as JSON for
tune-once/deploy-many, and threads ``plan.choices`` into the jitted forward
so each layer dispatches to its tuned kernel with its tuned parameters.
Results are memoized per (ConvSpec, mode).
"""
from __future__ import annotations

import json
import logging
import time
from dataclasses import asdict, dataclass, field

from repro.core.convspec import ConvSpec, FusedBlockSpec
from repro.core.dtypes import ACC_BYTES

log = logging.getLogger(__name__)

# TPU v5e per-chip constants (also used by the roofline analysis)
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9
VMEM_BYTES = 16 * 2 ** 20  # ~16 MB usable


@dataclass(frozen=True)
class Choice:
    algorithm: str
    params: tuple  # ((name, value), ...)
    est_time: float
    est_bytes: int
    est_flops: int
    vmem: int

    def to_dict(self) -> dict:
        d = asdict(self)
        d["params"] = [list(p) for p in self.params]
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Choice":
        d = dict(d)
        d["params"] = tuple((str(k), int(v)) for k, v in d["params"])
        return cls(**d)


def _el(spec):
    """Bytes per streamed element — shared rule, so dtype really moves the
    roofline (halving the width halves every byte term, which is what lets
    bf16 flip a site's winning algorithm). Accumulator terms stay ACC_BYTES
    wide regardless: kernels accumulate in fp32 and cast on the write."""
    return spec.element_size


def tunable(spec: ConvSpec) -> bool:
    """Whether a kernel family applies, i.e. the tuner has candidates.

    Three tunable classes, all covering stride 1 *and* 2 (every kernel
    family downsamples in-kernel, so strided backbone sites — the ResNet
    7x7/2 stem, stage-entry 3x3/2s, 1x1/2 projection shortcuts, MobileNet's
    strided depthwise layers — stay under the tuner):
      * dense spatial convs — the paper's five contenders at stride 1,
        the strided ilpm/direct variants at stride 2;
      * depthwise convs (groups == c, k = M·c for multiplier M >= 1);
      * dense 1x1 convs — the pointwise kernel (on the subsampled image
        at stride 2).

    Everything else (grouped non-depthwise convs, strides > 2) runs on the
    XLA reference path; such sites still get a plan entry with an ``xla``
    Choice.
    """
    if spec.depthwise:
        return spec.stride in (1, 2)
    if spec.groups != 1:
        return False  # general grouped conv: no kernel family yet
    if spec.r == 1 and spec.s == 1:
        return spec.stride in (1, 2)
    return spec.stride in (1, 2) and spec.r > 1 and spec.s > 1


def xla_choice(spec: ConvSpec, *, peak_flops=PEAK_FLOPS,
               hbm_bw=HBM_BW, epilogue=False) -> Choice:
    """Roofline estimate for the XLA escape-hatch path (untiled model).

    With ``epilogue=True`` the site wants a scale/bias/act applied; the
    escape hatch runs it as a separate XLA pass, so it pays an extra
    read+write of the output that the fused kernels do not.
    """
    bts = spec.bytes_min + (spec.epilogue_bytes if epilogue else 0)
    t = max(spec.flops / peak_flops, bts / hbm_bw)
    return Choice("xla", (), t, bts, spec.flops, 0)


def _candidates(spec: ConvSpec, epilogue=False):
    """Enumerate (algorithm, params, hbm_bytes, flops, vmem_working_set).

    Strided specs (stride 2) enumerate only the families whose kernels
    downsample in-kernel: ilpm/direct for spatial, pointwise for 1x1,
    depthwise for grouped. ``epilogue=True`` adds the fused scale/bias
    loads (2·K elements — noise, but kept honest) to every candidate; the
    *unfused* penalty is charged to `xla_choice`, not here, since every
    kernel family fuses in-kernel.
    """
    el = _el(spec)
    B, H, W, C, K, R, S = (spec.batch, spec.out_h, spec.out_w, spec.c,
                           spec.k, spec.r, spec.s)
    stride = spec.stride
    out = B * H * W * K * el
    ep = 2 * K * el if epilogue else 0  # fused scale+bias vector loads
    P = H * W
    cands = []

    # --- depthwise: channel-slab grid, image/filter/output cut together ---
    if spec.depthwise:
        m = spec.channel_multiplier
        hp = (H - 1) * stride + R
        wp = (W - 1) * stride + S
        img = B * hp * wp * C * el
        filt = R * S * K * el
        for tc in (128, 256, 512):
            tc = min(tc, K)
            vmem = hp * wp * -(-tc // m) * el + R * S * tc * el \
                + P * tc * ACC_BYTES
            cands.append(("depthwise", (("block_c", tc),),
                          img + filt + out + ep, spec.flops, vmem))
            if tc == K:
                break
        return cands

    # --- pointwise (1x1): image resident; K-tiled grid, single tap ---
    if R == 1 and S == 1:
        img = B * spec.h * spec.w * C * el  # full image even when strided
        filt = C * K * el
        for tk in (128, 256, 512):
            tk = min(tk, K)
            vmem = (img // max(B, 1)) + C * tk * el + P * tk * ACC_BYTES
            cands.append(("pointwise", (("block_k", tk),),
                          img + filt + out + ep, spec.flops, vmem))
            if tk == K:
                break
        return cands

    hp = (H - 1) * stride + R
    wp = (W - 1) * stride + S
    img = B * hp * wp * C * el
    filt = R * S * C * K * el

    # --- ilpm: image resident; filters streamed once; K-tiled grid ---
    for tk in (128, 256, 512):
        tk = min(tk, K)
        vmem = (img // max(B, 1)) + R * S * C * tk * el + P * tk * ACC_BYTES
        cands.append(("ilpm", (("block_k", tk),), img + filt + out + ep,
                      spec.flops, vmem))
        if tk == K:
            break

    # --- direct: filters resident; image row-bands streamed ---
    for th in (4, 8, 16):
        th = min(th, H)
        bh = (th - 1) * stride + R
        band = B * -(-H // th) * bh * wp * C * el
        vmem = bh * wp * C * el + filt + th * W * K * ACC_BYTES
        cands.append(("direct", (("block_h", th),), band + filt + out + ep,
                      spec.flops, vmem))
        if th == H:
            break

    if stride != 1:
        # im2col / libdnn / winograd have no strided kernels
        return cands

    # --- im2col: patch matrix round-trips HBM (the paper's 14.6x enemy);
    # its two-phase structure can't fuse the epilogue either, so it pays
    # the full unfused output round-trip, not the ~free vector loads ---
    patches = B * P * R * S * C * el
    ep_im2col = spec.epilogue_bytes if epilogue else 0
    vmem = min(P, 256) * R * S * C * el + R * S * C * 128 * el \
        + 256 * 128 * ACC_BYTES
    cands.append(("im2col", (),
                  img + patches + patches + filt + out + ep_im2col,
                  spec.flops, vmem))

    # --- libdnn: fused; unroll redone per K tile (index-math overhead) ---
    for tk in (128, 256):
        tk = min(tk, K)
        vmem = (img // max(B, 1)) + P * R * S * C * el // max(
            -(-K // tk), 1) + R * S * C * tk * el + P * tk * ACC_BYTES
        # model the redundant unroll as extra VMEM->VMEM work: ~10% flop tax
        cands.append(("libdnn", (("block_k", tk),), img + filt + out + ep,
                      int(spec.flops * 1.10), vmem))
        if tk == K:
            break

    # --- winograd F(2,3): 2.25x fewer MACs, 4x transform traffic ---
    if (R, S) == (3, 3) and H % 2 == 0 and W % 2 == 0:
        v_bytes = B * 16 * (H // 2) * (W // 2) * C * el
        m_bytes = B * 16 * (H // 2) * (W // 2) * K * el
        traffic = img + v_bytes + v_bytes + 16 * C * K * el + m_bytes \
            + m_bytes + out + ep
        flops = 2 * B * 16 * (H // 2) * (W // 2) * C * K  # the 16 GEMMs
        vmem = (img // max(B, 1)) + 16 * C * K * el \
            + min((H // 2) * (W // 2), 512) * (C + K) * el
        cands.append(("winograd", (), traffic, flops, vmem))
    return cands


def cost_model_select(spec: ConvSpec, *, peak_flops=PEAK_FLOPS, hbm_bw=HBM_BW,
                      vmem_bytes=VMEM_BYTES, epilogue=False) -> Choice:
    """Roofline-model pick; peak/bw overridable to tune for other devices.

    ``epilogue=True`` costs the fused conv+BN+act variants: free for the
    kernel families (in-kernel epilogue), an extra output round-trip for
    the XLA escape hatch.
    """
    if not tunable(spec):
        return xla_choice(spec, peak_flops=peak_flops, hbm_bw=hbm_bw,
                          epilogue=epilogue)
    best = None
    for algo, params, bts, flops, vmem in _candidates(spec, epilogue):
        if vmem > vmem_bytes:
            continue
        t = max(flops / peak_flops, bts / hbm_bw)
        if best is None or t < best.est_time:
            best = Choice(algo, params, t, bts, flops, vmem)
    assert best is not None, f"no feasible algorithm for {spec}"
    return best


def _synth_inputs(spec: ConvSpec):
    """Random padded input + filters matching the spec (measured mode).

    Group-aware: the filter depth is ``c // groups`` (1 for depthwise) and
    the padded image dims follow the stride ((out-1)*stride + r; the
    stride-1 case is the familiar h + r - 1). Pointwise specs get the
    unpadded image (r == 1 makes both formulas agree).
    """
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(spec.dtype) if spec.dtype != "float32" else jnp.float32
    hp = (spec.out_h - 1) * spec.stride + spec.r
    wp = (spec.out_w - 1) * spec.stride + spec.s
    x = jax.random.normal(jax.random.key(0),
                          (spec.batch, hp, wp, spec.c), dtype=dtype)
    w = jax.random.normal(
        jax.random.key(1),
        (spec.r, spec.s, spec.c_per_group, spec.k), dtype=dtype)
    return x, w


def measured_select(spec: ConvSpec, x=None, w=None, *, repeats=3,
                    noise_floor=0.5, epilogue=False) -> Choice:
    """Wall-clock tuning (the paper's procedure; interpret-mode here).

    ``x`` is the pre-padded input; synthesized from the spec when omitted.
    Each candidate is timed ``repeats`` times after a warm-up run and
    scored by its *minimum* (the standard low-noise estimator). Candidates
    that fail to run are logged and skipped, not silently eaten.

    Off-hardware, interpret-mode timings carry Python-dispatch noise that
    real TPU wall-clock does not, so the measured winner only displaces
    the cost model's pick when it is more than ``noise_floor`` (fraction)
    faster — the model acts as a prior under measurement noise. Set
    ``noise_floor=0`` on real hardware for pure wall-clock selection.

    Non-tunable specs short-circuit to the ``xla`` Choice without timing
    anything (there are no candidates to race). The spec's stride is
    threaded to the kernels that take it (depthwise); ``ops.dispatch``
    drops it for the stride-1-only dense kernels.
    """
    from repro.kernels import ops

    if not tunable(spec):
        return xla_choice(spec, epilogue=epilogue)
    if x is None or w is None:
        x, w = _synth_inputs(spec)

    best = None
    timed: dict[tuple, float] = {}
    for algo, params, bts, flops, vmem in _candidates(spec, epilogue):
        if vmem > VMEM_BYTES:
            continue
        try:
            ops.dispatch(algo, x, w, impl="pallas", stride=spec.stride,
                         **dict(params)).block_until_ready()  # warm-up
            ts = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                ops.dispatch(algo, x, w, impl="pallas", stride=spec.stride,
                             **dict(params)).block_until_ready()
                ts.append(time.perf_counter() - t0)
            t = min(ts)
        except Exception as e:
            log.warning("measured_select: candidate %s%r failed on %s: %s",
                        algo, dict(params), spec, e)
            continue
        timed[(algo, params)] = t
        if best is None or t < best.est_time:
            best = Choice(algo, params, t, bts, flops, vmem)
    assert best is not None, f"every candidate failed for {spec}"

    model = cost_model_select(spec, epilogue=epilogue)
    t_model = timed.get((model.algorithm, model.params))
    if t_model is not None and t_model <= best.est_time * (1 + noise_floor):
        return Choice(model.algorithm, model.params, t_model,
                      model.est_bytes, model.est_flops, model.vmem)
    return best


# ----------------------------------------------------------------------
# Block-level candidates: fused megakernels vs the per-layer chain.


def block_constituents(bspec: FusedBlockSpec, *, epilogue=True,
                       peak_flops=PEAK_FLOPS, hbm_bw=HBM_BW):
    """The per-layer Choices the fused block competes against — one tuned
    Choice per constituent conv, costed with the same epilogue flag the
    conv sites themselves tune under (apples-to-apples)."""
    return [cost_model_select(cs, peak_flops=peak_flops, hbm_bw=hbm_bw,
                              epilogue=epilogue)
            for _, cs in bspec.conv_specs()]


def block_baseline_time(bspec: FusedBlockSpec, *, epilogue=True,
                        peak_flops=PEAK_FLOPS, hbm_bw=HBM_BW) -> float:
    """Roofline time of the *unfused* path: the summed per-layer tuned
    choices plus — when the block carries a residual — the separate
    shortcut-add pass (a pure HBM read-modify-write the fused kernel
    folds into its output write for free)."""
    t = sum(c.est_time for c in block_constituents(
        bspec, epilogue=epilogue, peak_flops=peak_flops, hbm_bw=hbm_bw))
    return t + bspec.residual_pass_bytes / hbm_bw


def _block_candidates(bspec: FusedBlockSpec, epilogue=True,
                      peak_flops=PEAK_FLOPS, hbm_bw=HBM_BW):
    """Enumerate (algorithm, params, hbm_bytes, flops, vmem_working_set)
    for the fused kernel at this block.

    The byte estimate is the charging rule the whole tentpole hangs on:
    the fused candidate costs exactly the per-layer constituent sum MINUS
    ``bspec.saved_bytes`` — the expanded-tensor (inverted residual) or
    conv-output (residual conv) round-trips that now stay in VMEM, at the
    block's compute dtype. The residual-conv kernel still pays one HBM
    read of the shortcut operand (it is a *different* tensor, unlike the
    inverted residual's identity, which is the already-resident input);
    per-layer, that read is part of ``residual_pass_bytes`` charged to the
    baseline instead.

    ``block_m`` slabs must divide the expanded width (a ragged slab would
    double-count the projection accumulation), enumerated LARGEST first:
    every slab width moves the same bytes, so the first feasible
    candidate wins ties and the single-slab variant — whose projection
    reduction order is bitwise-identical to the per-layer chain — is
    preferred whenever it fits VMEM.
    """
    el = bspec.element_size
    constituents = block_constituents(bspec, epilogue=epilogue,
                                      peak_flops=peak_flops, hbm_bw=hbm_bw)
    base_bytes = sum(c.est_bytes for c in constituents)
    flops = sum(c.est_flops for c in constituents)
    B = bspec.batch
    OH, OW = bspec.out_h, bspec.out_w
    P = OH * OW
    cands = []
    if bspec.kind == "residual_conv":
        bts = base_bytes - bspec.saved_bytes \
            + el * B * P * bspec.cout  # the shortcut-branch read
        hp, wp = bspec.h + bspec.r - 1, bspec.w + bspec.s - 1
        for tk in (128, 256, 512):
            tk = min(tk, bspec.cout)
            vmem = hp * wp * bspec.cin * el \
                + bspec.r * bspec.s * bspec.cin * tk * el \
                + 2 * P * tk * el + P * tk * ACC_BYTES
            cands.append(("fused_residual_conv", (("block_k", tk),),
                          bts, flops, vmem))
            if tk == bspec.cout:
                break
        return cands
    bts = base_bytes - bspec.saved_bytes
    hp = (OH - 1) * bspec.stride + bspec.r
    wp = (OW - 1) * bspec.stride + bspec.s
    if bspec.expanded:
        tms = [bspec.mid] + [t for t in (512, 256, 128)
                             if t < bspec.mid and bspec.mid % t == 0]
    else:
        tms = [bspec.mid]  # t == 1: the slab is the unsliced input
    for tm in tms:
        vmem = el * (bspec.h * bspec.w * bspec.cin + bspec.cin * tm
                     + hp * wp * tm + bspec.r * bspec.s * tm
                     + tm * bspec.cout + P * tm) \
            + ACC_BYTES * P * (tm + bspec.cout)
        cands.append(("fused_inverted_residual", (("block_m", tm),),
                      bts, flops, vmem))
    return cands


def select_block(bspec: FusedBlockSpec, mode: str = "cost_model", *,
                 epilogue=True, peak_flops=PEAK_FLOPS, hbm_bw=HBM_BW,
                 vmem_bytes=VMEM_BYTES):
    """Fused-vs-per-layer decision for one block site -> Choice | None.

    Returns the fused kernel's Choice when its roofline time beats the
    per-layer baseline (tuned constituents + unfused shortcut-add pass)
    AND a feasible slab width exists; ``None`` means keep the per-layer
    plan at this site. Memoized like ``select``. Block selection is
    cost-model in both modes (wall-clock racing of whole fused blocks
    needs per-stage synth weights — a measured-mode follow-up); the
    *constituent* baseline already reflects the same cost model the
    per-layer sites tuned under, so the comparison stays consistent.
    """
    assert mode in MODES, f"unknown tuning mode {mode!r}; want one of {MODES}"
    key = (bspec, "block", epilogue)
    if key in _CACHE:
        return _CACHE[key]
    best = None
    for algo, params, bts, flops, vmem in _block_candidates(
            bspec, epilogue, peak_flops=peak_flops, hbm_bw=hbm_bw):
        if vmem > vmem_bytes:
            continue
        t = max(flops / peak_flops, bts / hbm_bw)
        if best is None or t < best.est_time:
            best = Choice(algo, params, t, bts, flops, vmem)
    baseline = block_baseline_time(bspec, epilogue=epilogue,
                                   peak_flops=peak_flops, hbm_bw=hbm_bw)
    if best is not None and best.est_time >= baseline:
        best = None  # fusion saves nothing here: keep per-layer
    _CACHE[key] = best
    return best


_CACHE: dict[tuple, Choice] = {}

MODES = ("cost_model", "measured")


def select(spec: ConvSpec, mode: str = "cost_model", *, repeats=3,
           noise_floor=0.5, epilogue=False) -> Choice:
    """Memoized selection — tune once, reuse per network.

    The cache key carries the measurement settings, so e.g. a careful
    ``repeats=10, noise_floor=0`` re-tune is not served a stale quick
    result; ``epilogue`` keys too, since it shifts the cost model.
    """
    assert mode in MODES, f"unknown tuning mode {mode!r}; want one of {MODES}"
    key = (spec, mode, epilogue) if mode == "cost_model" \
        else (spec, mode, repeats, noise_floor, epilogue)
    if key not in _CACHE:
        if mode == "measured":
            _CACHE[key] = measured_select(spec, repeats=repeats,
                                          noise_floor=noise_floor,
                                          epilogue=epilogue)
        else:
            _CACHE[key] = cost_model_select(spec, epilogue=epilogue)
    return _CACHE[key]


# ----------------------------------------------------------------------
# Tuning plans: tune once offline, serialize, deploy many times.

PLAN_VERSION = 2  # v2 adds the optional "blocks" section (fused megakernels)
_READABLE_VERSIONS = (1, 2)  # v1 plans (no blocks) still deploy


@dataclass
class TuningPlan:
    """Per-layer tuned choices for one network on one device.

    ``choices`` maps layer name -> Choice and is what the model forward
    consumes for per-layer dispatch; ``specs`` keeps the ConvSpec each
    choice was tuned for (provenance + validation on reload).

    ``block_choices``/``block_specs`` are the same contract one level up:
    block-site name (``<block>.block``) -> fused-megakernel Choice /
    FusedBlockSpec. A site present here is one the tuner decided to FUSE —
    its constituent convs keep their per-layer entries in ``choices`` (so
    the same plan deploys on engines without block support), but the
    forward dispatches the single fused kernel instead.
    """
    mode: str = "cost_model"
    specs: dict[str, ConvSpec] = field(default_factory=dict)
    choices: dict[str, Choice] = field(default_factory=dict)
    block_specs: dict[str, FusedBlockSpec] = field(default_factory=dict)
    block_choices: dict[str, Choice] = field(default_factory=dict)

    def algorithms(self) -> dict[str, str]:
        return {name: ch.algorithm for name, ch in self.choices.items()}

    def block_algorithms(self) -> dict[str, str]:
        return {name: ch.algorithm
                for name, ch in self.block_choices.items()}

    def to_json(self) -> str:
        layers = {name: {"spec": asdict(self.specs[name]),
                         "choice": self.choices[name].to_dict()}
                  for name in self.specs}
        blocks = {name: {"spec": asdict(self.block_specs[name]),
                         "choice": self.block_choices[name].to_dict()}
                  for name in self.block_specs}
        return json.dumps({"version": PLAN_VERSION, "mode": self.mode,
                           "layers": layers, "blocks": blocks}, indent=2)

    @classmethod
    def from_json(cls, text: str) -> "TuningPlan":
        d = json.loads(text)
        if d.get("version") not in _READABLE_VERSIONS:
            raise ValueError(f"unsupported plan version {d.get('version')!r}")
        plan = cls(mode=d["mode"])
        for name, layer in d["layers"].items():
            plan.specs[name] = ConvSpec(**layer["spec"])
            plan.choices[name] = Choice.from_dict(layer["choice"])
        for name, block in d.get("blocks", {}).items():  # absent in v1
            plan.block_specs[name] = FusedBlockSpec(**block["spec"])
            plan.block_choices[name] = Choice.from_dict(block["choice"])
        return plan

    def save(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    @classmethod
    def load(cls, path) -> "TuningPlan":
        with open(path) as f:
            return cls.from_json(f.read())


def xla_fallback_plan(named_specs, mode: str = "cost_model") -> TuningPlan:
    """Every site on the ``xla`` escape hatch — the **degraded-mode**
    plan the serving layer deploys when tuned Pallas dispatch (or a
    block-plan deploy) fails persistently.

    Each enumerated site gets ``xla_choice`` (costed as the fused
    conv+BN+act variant, matching how engines tune), and no block sites
    are fused — the forward routes every conv through the lax reference
    path, trading the paper's tuned kernels for staying up. Geometry and
    dtype come from the same ``named_specs`` enumeration a tuned plan
    uses, so engine plan-validation accepts the fallback unchanged.
    """
    plan = TuningPlan(mode=mode)
    for name, spec in named_specs:
        plan.specs[name] = spec
        plan.choices[name] = xla_choice(spec, epilogue=True)
    return plan


def build_plan(named_specs, mode: str = "cost_model", *, repeats=3,
               noise_floor=0.5, epilogue=False,
               block_specs=None) -> TuningPlan:
    """Tune every (name, ConvSpec) pair into a TuningPlan.

    ``named_specs`` is any iterable of ``(layer_name, ConvSpec)`` — the
    engine feeds it the model's ``conv_specs`` enumeration. Each spec goes
    through ``select``, so results come from (and populate) the module's
    mode-keyed memo cache: tuning N layers that share a shape costs one
    tuning run, and repeated ``build_plan`` calls in one process are free.
    Non-tunable sites (grouped non-depthwise convs, strides > 2) still
    get a plan entry with an ``xla`` Choice — the plan covers *every*
    enumerated site, and deployment falls back per-site, never wholesale.
    ``repeats``/``noise_floor`` only matter for ``mode="measured"``;
    ``epilogue=True`` costs each site as the fused conv+BN+act variant
    (what the model forwards actually run — the engine tunes this way).

    ``block_specs`` — an optional iterable of ``(block_site_name,
    FusedBlockSpec)`` (the model's ``block_specs`` enumeration) — turns on
    block-level tuning: each site goes through ``select_block``, and only
    sites where the fused megakernel beats the per-layer baseline get a
    ``block_choices`` entry. Per-conv entries are kept for every site
    either way, so the plan stays deployable with fusion ignored.
    """
    plan = TuningPlan(mode=mode)
    for name, spec in named_specs:
        plan.specs[name] = spec
        plan.choices[name] = select(spec, mode=mode, repeats=repeats,
                                    noise_floor=noise_floor,
                                    epilogue=epilogue)
    for name, bspec in (block_specs or ()):
        choice = select_block(bspec, mode=mode, epilogue=epilogue)
        if choice is not None:
            plan.block_specs[name] = bspec
            plan.block_choices[name] = choice
    return plan
