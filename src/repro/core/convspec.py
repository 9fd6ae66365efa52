"""ConvSpec: the key the autotuner and algorithm registry dispatch on.

``dtype`` is a first-class axis of the key: byte-traffic terms scale with
``repro.core.dtypes.element_size``, so a bf16 spec costs (and may tune)
differently from the same geometry in fp32, and two specs differing only
in dtype are distinct tuning keys.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.core.dtypes import element_size


@dataclass(frozen=True)
class ConvSpec:
    h: int
    w: int
    c: int
    k: int
    r: int = 3
    s: int = 3
    stride: int = 1
    batch: int = 1
    dtype: str = "float32"
    groups: int = 1  # feature groups; groups == c == k is depthwise
    # squeeze-and-excitation variants: ``pool`` — the conv also writes its
    # output's (batch, k) fp32 spatial sums (the SE's squeeze; depthwise);
    # ``gated`` — a (batch, c) gate scales the input's channels first (the
    # SE's excitation; 1x1). Each is a kernel variant, so a tuning key.
    pool: bool = False
    gated: bool = False

    def __post_init__(self):
        assert self.c % self.groups == 0, (self.c, self.groups)
        assert self.k % self.groups == 0, (self.k, self.groups)

    @property
    def c_per_group(self) -> int:
        """Input channels each output channel convolves (filter depth)."""
        return self.c // self.groups

    @property
    def depthwise(self) -> bool:
        """One filter column per input channel: groups == c, k = M·c for an
        integer channel multiplier M >= 1 (lax HWIO convention)."""
        return self.groups > 1 and self.groups == self.c \
            and self.k % self.c == 0

    @property
    def channel_multiplier(self) -> int:
        """Output channels per input channel of a depthwise conv (M)."""
        assert self.depthwise, self
        return self.k // self.c

    @property
    def out_h(self):
        return -(-self.h // self.stride)  # SAME: ceil(h / stride)

    @property
    def out_w(self):
        return -(-self.w // self.stride)

    @property
    def flops(self) -> int:
        """Useful MACs x2 (SAME padding): each of the k output channels
        contracts only its group's c/groups input channels."""
        return 2 * self.batch * self.out_h * self.out_w * self.r * self.s \
            * self.c_per_group * self.k

    @property
    def element_size(self) -> int:
        """Bytes per stored element (shared rule — int8 counts as 1, not
        the 4 the seed's hand-rolled ``2 if "16" in dtype`` gave it)."""
        return element_size(self.dtype)

    @property
    def bytes_min(self) -> int:
        """Compulsory traffic: image in + filters in + output out, and a
        squeeze-and-excitation variant's fp32 sums out or gate in."""
        el = self.element_size
        return el * (self.batch * self.h * self.w * self.c
                     + self.r * self.s * self.c_per_group * self.k
                     + self.batch * self.out_h * self.out_w * self.k) \
            + self.se_bytes

    @property
    def se_bytes(self) -> int:
        """The fp32 (batch, k) sums a ``pool`` conv writes and the fp32
        (batch, c) gate a ``gated`` conv reads."""
        return 4 * self.batch * (self.k * self.pool + self.c * self.gated)

    @property
    def epilogue_bytes(self) -> int:
        """Extra HBM traffic an *unfused* scale/bias/act pass costs: one
        read + one write of the conv output. Fused kernels pay ~none (the
        (k,) scale/bias vectors are noise); the cost model charges this to
        the XLA escape hatch when the call site wants an epilogue."""
        return 2 * self.element_size * self.batch * self.out_h \
            * self.out_w * self.k

    @classmethod
    def from_tensors(cls, x, w, stride, *, pool=False, gated=False):
        """Derive the spec from real tensors (NHWC image, HWIO filters).

        Group-aware: grouped filters carry ``c // groups`` channels on their
        input axis (depthwise weights are ``(r, s, 1, c)``), so ``groups`` is
        recovered as the ratio of image channels to filter depth rather than
        misreading the filter depth as the full input width.
        """
        b, h, ww, c = x.shape
        r, s, c_per_group, k = w.shape
        assert c % c_per_group == 0, (
            f"image channels {c} not divisible by filter depth {c_per_group}")
        return cls(h=h, w=ww, c=c, k=k, r=r, s=s, stride=stride, batch=b,
                   dtype=str(x.dtype), groups=c // c_per_group, pool=pool,
                   gated=gated)


@dataclass(frozen=True)
class FusedBlockSpec:
    """The tuning key for a *block-level* fused kernel candidate.

    Two kinds:

      * ``inverted_residual`` — MobileNet's expand(1x1) -> depthwise(RxS,
        stride 1|2) -> project(1x1) chain, optionally with the identity
        residual folded into the project write (``residual=True`` when
        stride == 1 and cin == cout). ``mid`` is the expanded width
        (``cin * t``); ``mid == cin`` models the t == 1 blocks that skip
        the expansion conv.
      * ``residual_conv`` — the second (stride-1) conv of a ResNet
        basic/bottleneck block with the shortcut add and the outer ReLU
        folded into its output write. ``mid`` is the conv's input width
        (``cin == mid`` by construction), ``r``/``s`` its filter size
        (3x3 for basic c2, 1x1 for bottleneck c3).

    ``se`` is the width of an inverted residual's squeeze-and-excitation
    gate (EfficientNet), 0 where the block has none. The fused kernel has
    no SE, so the tuner never fuses a block with ``se`` set (see
    ``autotune._block_candidates``); its constituents are the SE variants
    of the per-layer kernels.

    ``h``/``w`` are the *input* spatial dims of the fused region. ``dtype``
    is part of the key exactly as for ``ConvSpec``: the saved-round-trip
    accounting scales with the element width, so a bf16 block tunes (and
    validates on deploy) separately from fp32.
    """
    kind: str
    h: int
    w: int
    cin: int
    mid: int
    cout: int
    r: int = 3
    s: int = 3
    stride: int = 1
    residual: bool = False
    batch: int = 1
    dtype: str = "float32"
    se: int = 0

    def __post_init__(self):
        assert self.kind in ("inverted_residual", "residual_conv"), self.kind
        assert not self.se or self.kind == "inverted_residual", self
        if self.kind == "residual_conv":
            assert self.stride == 1 and self.residual, self
            assert self.cin == self.mid, self
        if self.residual and self.kind == "inverted_residual":
            assert self.stride == 1 and self.cin == self.cout, self

    @property
    def expanded(self) -> bool:
        """Whether the block has a distinct expansion conv (t > 1)."""
        return self.kind == "inverted_residual" and self.mid != self.cin

    @property
    def out_h(self) -> int:
        return -(-self.h // self.stride)  # SAME: ceil

    @property
    def out_w(self) -> int:
        return -(-self.w // self.stride)

    @property
    def element_size(self) -> int:
        return element_size(self.dtype)

    def conv_specs(self) -> tuple:
        """((name, ConvSpec), ...) — the per-layer constituents this fused
        block replaces, in execution order. The names match the model's
        ``conv_specs`` site suffixes (pw1/dw/pw2 or c2/c3) so the two
        enumerations stay cross-referenceable."""
        if self.kind == "residual_conv":
            suffix = "c2" if (self.r, self.s) != (1, 1) else "c3"
            return ((suffix, ConvSpec(
                h=self.h, w=self.w, c=self.mid, k=self.cout, r=self.r,
                s=self.s, batch=self.batch, dtype=self.dtype)),)
        parts = []
        if self.expanded:
            parts.append(("pw1", ConvSpec(
                h=self.h, w=self.w, c=self.cin, k=self.mid, r=1, s=1,
                batch=self.batch, dtype=self.dtype)))
        parts.append(("dw", ConvSpec(
            h=self.h, w=self.w, c=self.mid, k=self.mid, r=self.r, s=self.s,
            stride=self.stride, groups=self.mid, batch=self.batch,
            dtype=self.dtype, pool=bool(self.se))))
        parts.append(("pw2", ConvSpec(
            h=self.out_h, w=self.out_w, c=self.mid, k=self.cout, r=1, s=1,
            batch=self.batch, dtype=self.dtype, gated=bool(self.se))))
        return tuple(parts)

    @property
    def saved_bytes(self) -> int:
        """HBM round-trips the fusion eliminates, at the compute dtype.

        ``inverted_residual``: the expanded intermediates never leave VMEM
        — the expand output write + its (padded) depthwise read, and the
        depthwise output write + its project read. Blocks without an
        expansion conv (t == 1) only save the depthwise-output round-trip.

        ``residual_conv``: the conv-output round-trip of the separate
        shortcut-add pass (per-layer: write conv out, then read it back to
        add the identity; fused: the accumulator adds the identity before
        the single output write).
        """
        el = self.element_size
        if self.kind == "residual_conv":
            return 2 * el * self.batch * self.out_h * self.out_w * self.cout
        hp = (self.out_h - 1) * self.stride + self.r
        wp = (self.out_w - 1) * self.stride + self.s
        saved = 0
        if self.expanded:  # expand out (h*w) + padded depthwise in (hp*wp)
            saved += el * self.batch * self.mid * (self.h * self.w + hp * wp)
        # depthwise out + project in (both at the downsampled size)
        saved += 2 * el * self.batch * self.out_h * self.out_w * self.mid
        return saved

    @property
    def residual_pass_bytes(self) -> int:
        """Traffic of the *unfused* shortcut-add pass (read conv output,
        read identity, write sum) — charged to the per-layer baseline when
        ``residual`` is set, since that is what the fused write avoids."""
        if not self.residual:
            return 0
        return 3 * self.element_size * self.batch * self.out_h \
            * self.out_w * self.cout
