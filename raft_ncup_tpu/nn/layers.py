"""Base layers: convolution and normalization with PyTorch-matching
initialization and numerics.

Initialization parity matters for training-dynamics parity with the
reference, so ``Conv2d`` reproduces torch's defaults exactly:

- kernel: kaiming_uniform(a=sqrt(5))  => U(-b, b), b = sqrt(1 / fan_in)
- bias:   U(-1/sqrt(fan_in), 1/sqrt(fan_in))

and the encoders' explicit ``kaiming_normal_(mode='fan_out')`` (reference:
core/extractor.py:150-157) is available as ``init_mode='kaiming_out'``.

Mixed precision: params live in float32; when ``dtype`` is bfloat16 the
convolution computes in bfloat16 (the TPU analogue of the reference's CUDA
autocast regions), while norms always compute in float32.

How ``Conv2d`` is computed is chosen from the kernel's shape, stride,
dilation and groups and the operands' width (:func:`conv_form`). A
convolution with a thin side (the motion encoder's 7x7 over the 2 flow
channels, the flow head's 3x3 onto them) fills a sixty-fourth of an MXU tile
per kernel tap as ``conv_general_dilated``; with its taps folded into the
thin dimension it is ONE matrix product (PERF.md section 6, PR 29). The
encoders' 7x7 stride-2 stems over the 3 image channels are a stride-1
convolution over the plane's 2x2 phases instead (PR 48): folded into one
product they were faster alone and slower in the program.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_ncup_tpu.precision.sites import innermost_scope, record_site, suspended

# Policy-pinned dtypes (raft_ncup_tpu/precision/; docs/PRECISION.md).
# PARAM_DTYPE: master-weight storage — every PrecisionPolicy preset pins
# param_dtype to f32 (the policy constructor rejects anything else), so
# this module constant IS the policy's param dtype; modules cast params
# to the per-module compute ``dtype`` at use. NORM_DTYPE: normalization
# statistics always compute in f32 (PrecisionPolicy.norm_jnp pins it) —
# the standard mixed-precision exception. graftlint JGL009 forbids raw
# inline dtype literals in nn/ bodies; these named constants are the
# sanctioned routing.
PARAM_DTYPE = jnp.float32
NORM_DTYPE = jnp.float32


def _pair(v) -> tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


# The ``Conv2d`` call sites that took each form: a trace-time tally (as
# ``ops/nconv.dispatch_counts`` is), so that a measurement can tell which
# sites were folded and that every other one is the convolution it was.
# Sites are module paths below the module that was applied (fnet's and
# cnet's ``conv1`` are one name); sets, so a retrace changes nothing.
_conv_forms: dict[str, set] = {
    "folded_in": set(), "folded_out": set(), "phased_in": set(), "conv": set(),
}

# Widest thin side that is folded (:func:`conv_form`). Measured on a v5e,
# float32 `highest`, each site alone: device time of a call in ms, forward
# / forward + both cotangents, ``conv_general_dilated`` against the folded
# product (PERF.md section 6, PR 29; chiprun_out/pr29/fold_bench2.jsonl,
# fold_wide.jsonl):
#
#                      8 x 55 x 128                  6 x 46 x 96
#   site             conv          folded          conv          folded
#   7x7   2 -> 128   0.94 / 5.12   0.21 / 0.34     0.11 / 1.56   0.16 / 0.44
#   7x7   2 ->  64   0.92 / 4.76   0.18 / 0.30     0.11 / 1.54   0.15 / 0.39
#   3x3 256 ->   2   1.04 / 1.88   0.09 / 0.31     0.35 / 0.85   0.04 / 0.17
#   3x3 128 ->   2   0.52 / 0.95   0.04 / 0.15     0.17 / 0.50   0.02 / 0.08
#   7x7   8 -> 128   1.65 / 8.58   0.31 / 1.04
#   3x3   8 -> 128   0.40 / 1.56   0.08 / 0.16
#   3x3 256 ->   8   1.08 / 2.16   0.08 / 0.34
#   7x7 128 ->   8   2.82 / 7.13   0.26 / 0.71
#   7x7  16 -> 128   1.65 / 7.64   0.74 / 2.04
#   7x7  32 -> 128   1.66 / 7.34   1.42 / 4.53
#   7x7  64 -> 128   1.66 / 7.49   2.80 / 10.08   (folding has lost)
#   3x3 256 ->  16   1.08 / 2.30   0.14 / 0.54
#   3x3 256 ->  32   1.08 / 2.30   0.44 / 1.02
#   3x3 256 ->  64   1.09 / 2.48   0.72 / 1.90
#
# Folding stops winning between 32 and 64 channels of a 7x7 input (705 MB
# of shifted copies at 64) and is still ahead at 64 outputs of a 3x3. The
# one entry where the convolution is ahead under 32 is the 7x7's forward
# alone on the small plane (0.11 against 0.16), which only the training
# step runs, with the cotangents that make it 3.5x the other way. The
# rule stops at 8, the widest side measured at both kernel sizes in both
# forms, 3x or more ahead everywhere; the models' thin sites are 2 wide,
# and the weights net's 64 -> 32 stays the convolution it was.
#
# At stride (2, 2) (PR 48; the encoders' stem, 7x7 3 -> 64) a number of the
# site ALONE misleads. Device ms of a call, forward / forward + kernel
# cotangent (chiprun_out/pr48/stem_bench.jsonl): ``conv_general_dilated``
# against the strided patches folded into ONE ``dot_general``, cut as 7 + 7
# strided slices (K = 147) or from the plane's 2x2 phases by 4 + 4
# unit-stride slices with the kernel zero-extended to 8x8 (K = 192):
#
#                         float32 `highest`                             bfloat16
#   frames           conv          7+7 slices    phases          conv         7+7 slices    phases
#   16 x 440 x 1024  23.36 / 52.02  39.41 / 41.90  16.89 / 20.01   4.41 / 6.92  33.63 / 34.65  8.89 / 10.11
#    8 x 440 x 1024  23.62 / 53.02  20.07 / 21.31  11.32 / 12.86   3.55 / 5.04  15.94 / 17.10  5.61 /  7.21
#   12 x 368 x 768   14.45 / 32.32  18.48 / 19.58   8.57 /  9.97   2.66 / 4.21  15.17 / 16.16  3.99 /  5.41
#
# The phases' product is 1.4-2.1x ahead at float32 alone and it LOST in the
# program: `serve_sintel_raft` 49.562 -> 52.254 ms a pair, `stream_sintel_nc`
# 91.654 -> 94.338, `eval_sintel_nc_bf16` 43.718 -> 47.119
# (chiprun_out/pr48/A/results.jsonl). A ``dot_general`` over ``[B, 220, 512,
# K]`` is a convolution whose batch is W; the compiler lays its output out
# W-minor, the residual stage after it follows, and each of that stage's
# eight 3x3 convolutions then pays a relayout in and one out (3.5 ms each by
# the compiler's own estimate). So the forms are compared IN PLACE: both
# encoders with the input normalisation in one program (``model.encode``),
# device ms a call (chiprun_out/pr48/enc_bench.jsonl, enc_bench_final.jsonl):
#
#   form of the two stems                      float32, 8 pairs  bf16_infer, 16 pairs  float32 forward +
#                                              of 440 x 1024     of 440 x 1024         backward, 6 of 368 x 768
#   conv_general_dilated 7x7 stride 2          141.51            48.69                 334.45
#   phases, one dot_general (K = 192)          163.04
#   phases, convolution [4,4,12,64]            134.27            57.74                 341.49
#   ... cut by reshape + transpose             138.02
#   phases + 4 column shifts, [4,1,48,64]      126.74            72.03                 318.68
#   ... the 48 channels behind a barrier       117.27            61.52                 312.55   (kept)
#
# The convolution emitter runs 4 taps of 48 channels in 6.9 ms where 16 taps
# of 12 take 17.0 and 49 taps of 3 take 19.8 (fnet, 16 frames), and keeps the
# layouts of the stage after it. What is left is the relayout of the thin
# stacks into the convolution's channel-minor layout, lanes filled to a
# tenth: without the barrier the compiler fuses the concatenation into the
# convolution and relayouts each 12-channel shift on its own (4 copies, 9.5
# ms for fnet by its estimate; 1 copy of the 48 behind it). At one pass
# (bfloat16) the convolution is already 2.9 + 2.6 ms and every form loses:
# :func:`conv_form` reads the operands' itemsize.
FOLD_MAX_THIN = 8


def reset_conv_forms() -> None:
    for sites in _conv_forms.values():
        sites.clear()


def conv_forms() -> dict:
    """{'folded_in' | 'folded_out' | 'phased_in' | 'conv': sorted module
    paths} of every ``Conv2d`` call site traced since the last reset."""
    return {form: sorted(sites) for form, sites in _conv_forms.items()}


def conv_form(
    kernel_shape, stride=(1, 1), dilation=(1, 1), groups: int = 1,
    itemsize: Optional[int] = None,
) -> str:
    """How a convolution with this HWIO kernel is computed, decided from
    what the call can see: 'folded_in' (thin input at stride 1: the taps
    join the contraction), 'folded_out' (thin output at stride 1: the taps
    join the outputs), 'phased_in' (thin input at stride (2, 2) of operands
    ``itemsize`` = 4 bytes wide or more, the encoders' float32 stems: the
    plane's 2x2 phases and their column shifts join the channels,
    :func:`_conv_phased_in`) or 'conv' (``conv_general_dilated``: everything
    wide, any other stride, dilated, grouped, even or 1x1, and a strided
    thin input of narrower operands, which one MXU pass already runs in a
    sixth of the time, or of a width the caller does not state)."""
    kh, kw, cin, cout = kernel_shape
    stride = tuple(stride)
    if (
        stride not in ((1, 1), (2, 2)) or tuple(dilation) != (1, 1) or groups != 1
        or kh % 2 == 0 or kw % 2 == 0 or kh * kw == 1
    ):
        return "conv"
    if stride == (2, 2):
        return "phased_in" if cin <= FOLD_MAX_THIN and (itemsize or 0) >= 4 else "conv"
    if cin <= FOLD_MAX_THIN:
        return "folded_in"
    if cout <= FOLD_MAX_THIN:
        return "folded_out"
    return "conv"


def _window_hw(x: jax.Array, kernel: jax.Array, pad, stride=(1, 1)) -> tuple[int, int]:
    (ph, _), (pw, _) = pad
    return (
        (x.shape[1] + 2 * ph - kernel.shape[0]) // stride[0] + 1,
        (x.shape[2] + 2 * pw - kernel.shape[1]) // stride[1] + 1,
    )


def _conv_folded_in(x: jax.Array, kernel: jax.Array, pad, out_dtype=None) -> jax.Array:
    """Stride-1 convolution of a thin input: the kh * kw shifted copies of
    the zero-padded input side by side on the channel axis, contracted once
    with the kernel as a ``[kh * kw * Cin, Cout]`` matrix.

    The window's two shifts are taken apart, kw column shifts of the padded
    plane and then kh row shifts of that stack: kh + kw slices, not kh * kw.
    Every tap cut out of the plane on its own (49 slices into one
    concatenate) ran the 7x7 site in 1.59 ms at 6 x 46 x 96, against 0.19
    this way (a call on the host's clock; PERF.md section 6, PR 29)."""
    kh, kw, _, cout = kernel.shape
    ho, wo = _window_hw(x, kernel, pad)
    xpad = jnp.pad(x, ((0, 0), pad[0], pad[1], (0, 0)))
    cols = jnp.concatenate([xpad[:, :, kx : kx + wo] for kx in range(kw)], axis=-1)
    patches = jnp.concatenate([cols[:, ky : ky + ho] for ky in range(kh)], axis=-1)
    return jax.lax.dot_general(
        patches, kernel.reshape(-1, cout), (((3,), (0,)), ((), ())),
        preferred_element_type=out_dtype,
    )


def _conv_phased_in(x: jax.Array, kernel: jax.Array, pad, out_dtype=None) -> jax.Array:
    """Stride-(2, 2) convolution of a thin input as a stride-1 convolution
    over its 2x2 phases: ``xpad[2i + ky, 2j + kx]`` with ``ky = 2a + py``,
    ``kx = 2b + px`` is ``phases[i + a, j + b, (py, px)]``, so the padded
    plane is cut into its four phases once (``[B, Ho + ah - 1, Wo + aw - 1,
    4 Cin]``, ah = ceil(kh / 2)), the aw column shifts of that stack join
    the channels too (``4 aw Cin``: 48 for the encoders' 7x7 over 3), and
    ``conv_general_dilated`` walks the ah row taps with the kernel
    zero-extended to ``[2 ah, 2 aw]`` and regrouped ``[ah, 1, 4 aw Cin,
    Cout]``. The extension's rows are exact zeros against finite data: the
    same sums in another order.

    Why a convolution and not PR 29's one product: a ``dot_general`` over the
    whole patch stack hands the residual stages after it another layout, and
    every convolution there then pays two relayouts (table above
    ``FOLD_MAX_THIN``). Why the barrier: without it the compiler fuses the
    concatenation into the convolution and relayouts each of the aw shifted
    12-channel stacks on its own, lanes filled to a tenth, four times."""
    kh, kw, cin, cout = kernel.shape
    ah, aw = (kh + 1) // 2, (kw + 1) // 2
    ho, wo = _window_hw(x, kernel, pad, (2, 2))
    hp, wp = 2 * (ho + ah - 1), 2 * (wo + aw - 1)
    (pt, _), (pl, _) = pad
    xpad = jnp.pad(x, (
        (0, 0), (pt, hp - x.shape[1] - pt), (pl, wp - x.shape[2] - pl), (0, 0)
    ))
    # ``lax.slice``: a stepped ``jnp`` index lowers to a gather.
    phases = jnp.concatenate([
        jax.lax.slice(xpad, (0, py, px, 0), xpad.shape, (1, 2, 2, 1))
        for py in (0, 1) for px in (0, 1)
    ], axis=-1)
    cols = jax.lax.optimization_barrier(
        jnp.concatenate([phases[:, :, b : b + wo] for b in range(aw)], axis=-1)
    )
    taps = jnp.pad(kernel, ((0, 2 * ah - kh), (0, 2 * aw - kw), (0, 0), (0, 0)))
    taps = taps.reshape(ah, 2, aw, 2, cin, cout).transpose(0, 2, 1, 3, 4, 5)
    return jax.lax.conv_general_dilated(
        cols, taps.reshape(ah, 1, 4 * aw * cin, cout), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), preferred_element_type=out_dtype,
    )


def _conv_folded_out(x: jax.Array, kernel: jax.Array, pad, out_dtype=None) -> jax.Array:
    """Stride-1 convolution onto a thin output, the transpose of
    :func:`_conv_folded_in`: one product with the kernel as a ``[kh * kw *
    Cout, Cin]`` matrix, then the kh * kw thin result planes shifted and
    summed, rows first and columns on the row sums (kh + kw slices).

    The product puts the taps in front, ``[kh, kw, Cout, B, H, W]``: whole
    (H, W) planes to shift, as the tap sums of ``ops/nconv.py`` are."""
    kh, kw, cin, cout = kernel.shape
    ho, wo = _window_hw(x, kernel, pad)
    kmat = kernel.transpose(0, 1, 3, 2).reshape(kh * kw * cout, cin)
    planes = jax.lax.dot_general(
        kmat, x, (((1,), (3,)), ((), ())), preferred_element_type=out_dtype
    )
    planes = planes.reshape(kh, kw, cout, *x.shape[:3])
    planes = jnp.pad(planes, ((0, 0),) * 4 + (pad[0], (0, 0)))
    rows = sum(planes[ky, ..., ky : ky + ho, :] for ky in range(kh))
    rows = jnp.pad(rows, ((0, 0),) * 4 + (pad[1],))
    out = sum(rows[kx, ..., kx : kx + wo] for kx in range(kw))
    return out.transpose(1, 2, 3, 0)


def conv2d(
    x: jax.Array, kernel: jax.Array, pad, *, site: str, stride=(1, 1),
    dilation=(1, 1), groups: int = 1, out_dtype=None,
) -> jax.Array:
    """NHWC x HWIO convolution in the form :func:`conv_form` gives its
    kernel, tallied under ``site``. ``out_dtype``: the dtype the products'
    accumulator is handed out in (None: the operands')."""
    form = conv_form(kernel.shape, stride, dilation, groups, x.dtype.itemsize)
    _conv_forms[form].add(site)
    record_site(site, x.dtype, out_dtype)
    if form == "folded_in":
        return _conv_folded_in(x, kernel, pad, out_dtype)
    if form == "phased_in":
        return _conv_phased_in(x, kernel, pad, out_dtype)
    if form == "folded_out":
        if out_dtype is None and x.dtype != PARAM_DTYPE:
            # Narrow operands: the taps' planes are added in the
            # accumulator's dtype and the sum is rounded once, as the one
            # product of the two other forms is (docs/PRECISION.md).
            folded = functools.partial(_conv_folded_out, pad=pad)
            return _wide_out(folded)(x, kernel).astype(x.dtype)
        return _conv_folded_out(x, kernel, pad, out_dtype)
    return jax.lax.conv_general_dilated(
        x,
        kernel,
        window_strides=stride,
        padding=pad,
        rhs_dilation=dilation,
        dimension_numbers=jax.lax.conv_dimension_numbers(
            x.shape, kernel.shape, ("NHWC", "HWIO", "NHWC")
        ),
        feature_group_count=groups,
        preferred_element_type=out_dtype,
    )


def _uniform_init(bound: float):
    def init(key, shape, dtype=PARAM_DTYPE):
        return jax.random.uniform(key, shape, dtype, minval=-bound, maxval=bound)

    return init


class Conv2d(nn.Module):
    """NHWC convolution with torch-compatible padding and init.

    Default padding is kernel//2 per axis — the scheme every conv in the
    reference uses (explicit ``padding=k//2`` at each call site).
    """

    features: int
    kernel_size: Any = 3
    stride: Any = 1
    dilation: Any = 1
    padding: Optional[Any] = None
    use_bias: bool = True
    groups: int = 1
    init_mode: str = "torch"  # 'torch' | 'kaiming_out'
    dtype: Any = None  # compute dtype; None = input dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        dh, dw = _pair(self.dilation)
        cin = x.shape[-1]
        fan_in = (cin // self.groups) * kh * kw

        if self.init_mode == "torch":
            kinit = _uniform_init(math.sqrt(1.0 / fan_in))
        elif self.init_mode == "kaiming_out":
            fan_out = (self.features // self.groups) * kh * kw
            kinit = nn.initializers.normal(stddev=math.sqrt(2.0 / fan_out))
        else:
            raise ValueError(f"unknown init_mode: {self.init_mode!r}")

        kernel = self.param(
            "kernel", kinit, (kh, kw, cin // self.groups, self.features), PARAM_DTYPE
        )

        if self.padding is None:
            ph, pw = kh // 2, kw // 2
        else:
            ph, pw = _pair(self.padding)
        # torch pads k//2 for odd kernels; with dilation the reference
        # computes pad = k//2 + (k-1)(d-1)/2 at call sites — callers pass
        # that explicitly via `padding`.
        pad = ((ph, ph), (pw, pw))

        cdt = self.dtype or x.dtype
        x, kernel = x.astype(cdt), kernel.astype(cdt)
        y = conv2d(
            x, kernel, pad, site="/".join(self.path), stride=(sh, sw),
            dilation=(dh, dw), groups=self.groups,
        )
        if self.use_bias:
            bias = self.param(
                "bias",
                _uniform_init(1.0 / math.sqrt(fan_in)),
                (self.features,),
                PARAM_DTYPE,
            )
            y = y + bias.astype(cdt)
        return y


def _wide_out(conv):
    """``conv(x, kernel)`` of narrow operands with its accumulator handed out
    in ``PARAM_DTYPE``. jax transposes a convolution with a
    ``preferred_element_type`` into one of the wide cotangent with the
    narrow operand, which ``conv_general_dilated`` refuses; the cotangent
    is rounded to the operands' dtype first, as it would be had the
    forward rounded. Which of the three functions jax traces, and when
    (the primal at the call, the rules often after the scopes around the
    call have closed), depends on the transformation above: the product-site
    tally is suspended in all three, and the caller records the site."""

    @jax.custom_vjp
    def wide(x, kernel):
        with suspended():
            return conv(x, kernel, out_dtype=PARAM_DTYPE)

    def bwd(operands, g):
        with suspended():
            return jax.vjp(conv, *operands)[1](g.astype(operands[0].dtype))

    wide.defvjp(lambda x, kernel: (wide(x, kernel), (x, kernel)), bwd)
    return wide


class SplitConv2d(nn.Module):
    """A stride-1 ``Conv2d`` over ``in_features`` channels of which the rows
    ``fixed = (lo, hi)`` read an input that is the same in every call: the
    GRU's context features over the refinement loop.

    A convolution is linear in its input channels, so it is two sums.
    :meth:`context`, once before the loop, takes the kernel apart and
    convolves the fixed input with its rows; ``__call__``, in the loop,
    convolves the other channels with theirs and adds that term. The
    parameters are ``Conv2d``'s over the whole width, name for name, shape
    for shape and draw for draw (``kernel`` ``(kh, kw, in_features,
    features)`` and ``bias``, fan-in of the whole width), so a checkpoint
    cannot tell the two apart. Each product takes its form from
    :func:`conv_form`, tallied as ``<path>/context`` and ``<path>/step``.
    """

    features: int
    kernel_size: Any
    in_features: int
    fixed: tuple[int, int]
    dtype: Any = None  # compute dtype; None = input dtype

    def setup(self):
        kh, kw = _pair(self.kernel_size)
        fan_in = self.in_features * kh * kw
        self.kernel = self.param(
            "kernel", _uniform_init(math.sqrt(1.0 / fan_in)),
            (kh, kw, self.in_features, self.features), PARAM_DTYPE,
        )
        self.bias = self.param(
            "bias", _uniform_init(1.0 / math.sqrt(fan_in)), (self.features,),
            PARAM_DTYPE,
        )

    def _conv(self, x, kernel, part: str) -> jax.Array:
        """The product of one part, handed out in the parameters' dtype:
        the two parts are added there, and rounded to a narrower compute
        dtype once, after."""
        kh, kw = kernel.shape[:2]
        site = "/".join(self.path + (part,))
        conv = functools.partial(
            conv2d, pad=((kh // 2, kh // 2), (kw // 2, kw // 2)), site=site
        )
        if x.dtype == PARAM_DTYPE:
            return conv(x, kernel)
        record_site(site, x.dtype, PARAM_DTYPE)
        return _wide_out(conv)(x, kernel)

    def context(self, fixed_input: jax.Array) -> tuple[jax.Array, jax.Array]:
        """``(the kernel's other rows, conv(fixed_input, its rows))``: what
        ``__call__`` takes as ``ctx``."""
        lo, hi = self.fixed
        cdt = self.dtype or fixed_input.dtype
        kernel = self.kernel.astype(cdt)
        term = self._conv(fixed_input.astype(cdt), kernel[:, :, lo:hi], "context")
        rest = jnp.concatenate([kernel[:, :, :lo], kernel[:, :, hi:]], axis=2)
        return rest, term

    def __call__(self, x: jax.Array, ctx) -> jax.Array:
        """``x``: the input's channels without the fixed rows. The bias is
        added here, not into the term: every elementwise operation on the
        result then sits in the loop body, ONE fused expression whether the
        loop is a scan, a ``while_loop`` or a single iteration the compiler
        inlines (``tests/test_earlyexit.py`` holds a frozen lane bitwise to
        the plain forward of another executable)."""
        rest, term = ctx
        cdt = self.dtype or x.dtype
        return (self._conv(x.astype(cdt), rest, "step") + term + self.bias).astype(cdt)


class ConvTranspose2d(nn.Module):
    """NHWC transposed convolution matching ``nn.ConvTranspose2d`` (used by
    the UNet weights-estimation net, reference: core/interp_weights_est.py:135).
    """

    features: int
    kernel_size: Any = 2
    stride: Any = 2
    use_bias: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        cin = x.shape[-1]
        # torch ConvTranspose2d weight is (in, out, kh, kw); its default
        # kaiming_uniform(a=sqrt(5)) reads fan_in from dim 1: out * kh * kw.
        fan_in = self.features * kh * kw
        # Stored (kh, kw, out, in) — torch's (in, out, kh, kw) under the
        # same OIHW->HWIO transpose the importer applies to regular convs.
        # transpose_kernel=True makes lax.conv_transpose the exact gradient
        # of a forward conv, matching nn.ConvTranspose2d bit-for-bit.
        kernel = self.param(
            "kernel",
            _uniform_init(math.sqrt(1.0 / fan_in)),
            (kh, kw, self.features, cin),
            PARAM_DTYPE,
        )
        cdt = self.dtype or x.dtype
        record_site("/".join(self.path), cdt)
        y = jax.lax.conv_transpose(
            x.astype(cdt),
            kernel.astype(cdt),
            strides=(sh, sw),
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            transpose_kernel=True,
        )
        if self.use_bias:
            bias = self.param(
                "bias",
                _uniform_init(1.0 / math.sqrt(fan_in)),
                (self.features,),
                PARAM_DTYPE,
            )
            y = y + bias.astype(cdt)
        return y


class BatchNormTrain(nn.Module):
    """BatchNorm in training mode as ``torch.nn.BatchNorm2d`` computes it,
    under flax ``nn.BatchNorm``'s variable names (``scale`` / ``bias``,
    ``batch_stats``: ``mean`` / ``var``), so a tree serves both. The
    statistics are taken in float32 over the whole batch and every position,
    mean first and the variance about it; the input is normalised by the
    BIASED variance, the gradient flows through both, and the running pair
    moves by ``1 - momentum`` toward the batch mean and the UNBIASED variance
    (torch's convention; flax's ``BatchNorm`` keeps the biased one). The
    statistics and the running update lie under the scope ``<innermost
    raft.* scope>.bn_stats`` (``raft.cnet.bn_stats``), which a capture by
    scope splits from the convolutions around them (docs/OBSERVABILITY.md).
    """

    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), PARAM_DTYPE)
        bias = self.param("bias", nn.initializers.zeros, (c,), PARAM_DTYPE)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((c,), NORM_DTYPE)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((c,), NORM_DTYPE)
        )
        outer = innermost_scope()
        with jax.named_scope(f"{outer}.bn_stats" if outer else "bn_stats"):
            axes = tuple(range(x.ndim - 1))
            n = math.prod(x.shape[:-1])
            mean = jnp.mean(x, axes)
            var = jnp.mean(jnp.square(x - mean), axes)
            if not self.is_initializing():
                keep = self.momentum
                ra_mean.value = keep * ra_mean.value + (1.0 - keep) * mean
                ra_var.value = keep * ra_var.value + (1.0 - keep) * var * (
                    n / max(n - 1, 1)
                )
        return (x - mean) * jax.lax.rsqrt(var + self.epsilon) * scale + bias


class Norm(nn.Module):
    """Normalization factory matching the reference's norm_fn choices
    (reference: core/extractor.py:16-38,123-133).

    - 'group': GroupNorm(affine), eps 1e-5.
    - 'batch': BatchNorm, momentum 0.1 (torch) == flax momentum 0.9,
       eps 1e-5. Eval/frozen mode uses running stats (flax's module);
       training mode is :class:`BatchNormTrain`.
    - 'instance': per-channel, per-sample normalization without affine
       (torch InstanceNorm2d default affine=False).
    - 'none': identity.

    Norm math always runs in float32 regardless of activation dtype.
    """

    kind: str
    num_groups: Optional[int] = None

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool = False) -> jax.Array:
        in_dtype = x.dtype
        x32 = x.astype(NORM_DTYPE)
        if self.kind == "none":
            return x
        if self.kind == "group":
            y = nn.GroupNorm(num_groups=self.num_groups, epsilon=1e-5)(x32)
        elif self.kind == "instance":
            y = nn.GroupNorm(
                num_groups=x.shape[-1], epsilon=1e-5, use_bias=False, use_scale=False
            )(x32)
        elif self.kind == "batch" and train:
            y = BatchNormTrain(momentum=0.9, epsilon=1e-5, name="BatchNorm_0")(x32)
        elif self.kind == "batch":
            y = nn.BatchNorm(
                use_running_average=True, momentum=0.9, epsilon=1e-5
            )(x32)
        else:
            raise ValueError(f"unknown norm kind: {self.kind!r}")
        return y.astype(in_dtype)
