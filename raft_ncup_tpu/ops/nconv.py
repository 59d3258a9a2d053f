"""Normalized-convolution primitives (the math under NCUP).

The core op is a pair of convolutions sharing one kernel with non-negative
weights (reference: core/nconv_modules.py:164-199):

    out  = conv(data * conf, w) / (conv(conf, w) + eps) [+ bias]
    cout = conv(conf, w) / sum(w)        # propagated confidence

plus the confidence-aware downsampling (max-pool confidence, gather data at
the confidence argmax, reference: core/nconv_modules.py:94-104) and the
zero-stuffing scatter that lifts low-res data onto the high-res grid
(reference: core/upsampler.py:208).

Non-negativity is enforced by a softplus reparameterization — the
functional analogue of the reference's forward-pre-hook ``EnforcePos``
machinery (core/nconv_modules.py:218-269); no hooks needed in JAX: the
positive weight is simply recomputed from the raw parameter every call.

How ``conv`` is computed is chosen from the kernel's shape
(:func:`tap_form`). NCUP's own layers have 1-8 input and 1-4 output
channels on full-resolution planes: an MXU convolution fills about a
hundredth of a tile with them and, at float32 `highest`, pays six bf16
passes for it (66.7 ms for one NCUP forward over 12 planes of 368x768 on a
v5e). They are computed as a sum over the kernel's taps of shifted planes
times scalar weights, in float32 on the vector units, forward and both
cotangents (3.3 ms; PERF.md section 6, PR 27). Wide kernels, strides,
groups and even kernels stay ``conv_general_dilated``, cotangents and
all; no NCUP configuration issues one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Trace-time dispatch tally for the fused-kernel path: callers that label a
# measurement "nconv=pallas" (chip_smoke.py) must be able to tell whether the
# fused kernel actually ran or every call silently fell back to XLA
# (ADVICE r3: a baseline pinned under '+nconv_pallas' that measured the
# XLA path would poison every later comparison).
# 'taps' / 'mxu': the form each call site outside the fused kernel took.
_dispatch_counts = {"fused": 0, "fallback": 0, "taps": 0, "mxu": 0}

# Largest Cin * Cout computed as a tap sum (:func:`tap_form`). Measured on
# a v5e at the Sintel training plane (12 x 368x768, float32 `highest`; one
# nconv2d = two convolutions and the divide; ms forward, forward + both
# cotangents, the MXU's with PR 26's per-tap einsum for the kernel's;
# PERF.md section 6, PR 27, chiprun_out/pr27/ncup_bench.jsonl):
#
#   Cin x Cout      3x3 taps     3x3 MXU      5x5 taps     5x5 MXU
#     2 (1->2)      1.4, 1.7    12.5, 25.3    1.5, 2.5    26.7, -
#     4 (2->2)      1.5, 2.2    13.4, 30.0    1.8, 3.9    24.4, 60.4
#     8 (4->2|2->4) 1.8, 4.2    15.0, 32.8    2.6, 6.0    26.2, -
#    16 (4->4)      2.5, 5.6    16.3, 35.8    3.9, 10.9   27.8, -
#    32 (8->4)      4.7, 16.7   19.3, 44.2    8.2, 36.7   39.9, 81.8
#    64 (8->8)      7.4, 22.5   20.8, 50.3   13.7, -      47.3, -
#   256 (16->16)   93.1, -      65.3, -
#
# The tap sum wins up to 64 and has lost by 256; its compile time grows
# with k*k*Cin*Cout (5x5 8->4 with cotangents: 40 s on the chip, 3x3 16->16
# forward alone 48 s), so the rule stops at 32, the widest NCUP site
# (`channels_to_batch=False`'s 8 -> 4 decoder), where both kernel sizes
# were measured forward and backward.
TAP_MAX_CIN_X_COUT = 32


def reset_dispatch_counts() -> None:
    for key in _dispatch_counts:
        _dispatch_counts[key] = 0


def dispatch_counts() -> dict:
    """Copy of the {'fused', 'fallback', 'taps', 'mxu'} tally since the
    last reset: 'fused' / 'fallback' for calls that asked for the Pallas
    kernel, 'taps' / 'mxu' for the engine of every call the kernel did not
    run (a fallback counts there too).
    Counts trace-time decisions (one per distinct nconv2d call site per
    TRACE), not runtime executions — extra traces in the same process
    (custom_vjp backward, retraces, concurrent threads) inflate the
    tally, so values are only interpretable between a reset and a single
    lowering in a single thread (the callers' discipline)."""
    return dict(_dispatch_counts)


def positivity(raw: jax.Array, pos_fn: str = "softplus") -> jax.Array:
    """Map a raw parameter to a non-negative kernel.

    Reference: core/nconv_modules.py:254-269 (``_pos``). The softplus uses
    beta=10: softplus_10(x) = log(1 + exp(10 x)) / 10.
    """
    pos_fn = pos_fn.lower()
    if pos_fn == "softplus":
        return jax.nn.softplus(10.0 * raw) / 10.0
    if pos_fn == "exp":
        return jnp.exp(raw)
    if pos_fn == "sigmoid":
        return jax.nn.sigmoid(raw)
    if pos_fn == "softmax":
        # Per-output-channel softmax over (kh, kw, in).
        o = raw.shape[-1]
        flat = raw.reshape(-1, o)
        return jax.nn.softmax(flat, axis=0).reshape(raw.shape)
    raise ValueError(f"unknown pos_fn: {pos_fn!r}")


def nconv2d(
    data: jax.Array,
    conf: jax.Array,
    weight: jax.Array,
    bias: jax.Array | None = None,
    *,
    eps: float = 1e-20,
    stride: int = 1,
    groups: int = 1,
    propagate_conf: bool = True,
    impl: str | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    """Normalized convolution with confidence propagation.

    Args:
      data, conf: (B, H, W, Cin) NHWC.
      weight: (kh, kw, Cin/groups, Cout) HWIO, already non-negative (apply
        :func:`positivity` first).
      bias: (Cout,) or None.
      impl: 'xla' (two convs + divide, each a float32 tap sum on the
        vector units or an MXU convolution by :func:`tap_form`) or 'pallas'
        (fused single-pass forward kernel, raft_ncup_tpu.ops.nconv_pallas)
        — default comes from env RAFT_NCUP_NCONV_IMPL ('xla': on a v5e the
        tap sums run one NCUP forward in 3.3 ms at 12 x 368x768 and 8.3 ms
        at 16 x 440x1024, the kernel in 4.4 and 10.9, PERF.md section 6,
        PR 27). Off the TPU 'pallas' runs the XLA composition with a
        warning (and counts a fallback); on the TPU a call outside the
        kernel's surface (stride/groups/even kernels, rows too wide for
        the VMEM budget) raises.
    Returns:
      (out, conf_out), both (B, H', W', Cout); SAME padding for odd kernels
      (reference pads kernel//2, core/nconv_modules.py:143-144).
    """
    from raft_ncup_tpu.utils.knobs import knob_str

    impl = impl or knob_str("RAFT_NCUP_NCONV_IMPL")
    if impl == "pallas":
        from raft_ncup_tpu.ops import nconv_pallas as npk
        from raft_ncup_tpu.utils.runtime import is_tpu_backend

        on_tpu = is_tpu_backend()  # Mosaic compiles for the TPU only
        in_surface = npk.supported(
            weight.shape, stride, groups
        ) and npk.fits_vmem(
            data.shape[1], data.shape[2], data.shape[3],
            weight.shape[-1], weight.shape[0],
        )
        if on_tpu and in_surface:
            _dispatch_counts["fused"] += 1
            out, conf_out = npk.nconv2d_fused(data, conf, weight, bias, eps)
            return out, (conf_out if propagate_conf else None)
        _dispatch_counts["fallback"] += 1
        msg = (
            "nconv impl='pallas' cannot run the fused kernel for shape "
            f"data={tuple(data.shape)} weight={tuple(weight.shape)} "
            f"stride={stride} groups={groups} (on tpu: {on_tpu}, in the "
            f"kernel's surface: {in_surface})"
        )
        if on_tpu:
            # On the chip a call that reaches no kernel is an error, not
            # XLA under the kernel's name.
            raise RuntimeError(msg + " — use impl='xla' for this call")
        import warnings

        warnings.warn(
            msg + " — running the XLA composition; measurements labeled "
            "nconv=pallas did NOT run the fused kernel here",
            stacklevel=2,
        )
    kh, kw = weight.shape[0], weight.shape[1]
    pad = ((kh // 2, kh // 2), (kw // 2, kw // 2))
    dn = jax.lax.conv_dimension_numbers(data.shape, weight.shape, ("NHWC", "HWIO", "NHWC"))
    taps = tap_form(weight.shape, stride, groups)
    _dispatch_counts["taps" if taps else "mxu"] += 1

    def conv(x: jax.Array) -> jax.Array:
        if taps:
            return _conv_same(x, weight)
        return jax.lax.conv_general_dilated(
            x,
            weight,
            window_strides=(stride, stride),
            padding=pad,
            dimension_numbers=dn,
            feature_group_count=groups,
        )

    denom = conv(conf)
    nomin = conv(data * conf)
    out = nomin / (denom + eps)
    if bias is not None:
        out = out + bias
    if propagate_conf:
        # conf_out = conv(conf) / sum_k(w) per output channel
        # (reference: core/nconv_modules.py:180-194).
        s = weight.sum(axis=(0, 1, 2))
        conf_out = denom / s
    else:
        conf_out = None
    return out, conf_out


def tap_form(weight_shape, stride: int = 1, groups: int = 1) -> bool:
    """Whether a convolution is computed as a tap sum on the vector units
    (True) or as ``conv_general_dilated`` on the MXU: decided from what the
    call can see, the kernel's shape, stride and groups."""
    kh, kw, cin, cout = weight_shape
    return (
        stride == 1 and groups == 1 and kh % 2 == 1 and kw % 2 == 1
        and cin * cout <= TAP_MAX_CIN_X_COUT
    )


@jax.jit
def _conv_same_taps(x: jax.Array, weight: jax.Array) -> jax.Array:
    """``out[..., o] = sum_{kx} sum_{ky, c} xpad[:, ky:ky+H, kx:kx+W, c] *
    w[ky, kx, c, o]``: taps outermost, channels inside, every product and
    sum in the input's float32, on whole (B, H, W) planes (W on lanes, H
    on sublanes): elementwise fusions, no ``convolution``, no ``dot``.

    The window's two shifts are taken apart: the kh row shifts are applied
    to the input's planes, the kw column shifts to the sums over a kernel
    column. A shift by a few lanes or sublanes is the expensive part of a
    tap on the chip, and the compiler keeps a shifted copy of a plane for
    every shifted slice it is given: kh * Cin + kw * Cout copies this way,
    kh * kw * Cin with every tap cut out of the padded plane (5.4x slower
    at the 5x5 2 -> 2 layer, PERF.md section 6, PR 27)."""
    kh, kw, cin, cout = weight.shape
    h, w = x.shape[1], x.shape[2]
    xpad = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (0, 0), (0, 0)))
    rows = [[xpad[:, ky : ky + h, :, c] for c in range(cin)] for ky in range(kh)]
    outs = []
    for o in range(cout):
        out = None
        for kx in range(kw):
            col = None
            for ky in range(kh):
                for c in range(cin):
                    term = rows[ky][c] * weight[ky, kx, c, o]
                    col = term if col is None else col + term
            col = jnp.pad(col, ((0, 0), (0, 0), (kw // 2, kw // 2)))[:, :, kx : kx + w]
            out = col if out is None else out + col
        outs.append(out)
    return jnp.stack(outs, axis=-1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _kernel_cotangent(x: jax.Array, g: jax.Array, kh: int, kw: int) -> jax.Array:
    """``dw[ky, kx, c, o] = sum_bhw xpad[b, h+ky, w+kx, c] * g[b, h, w, o]``,
    one contraction per tap: multiply + reduce over whole planes in float32
    (a ``dot`` with a 1x2 to 8x4 result over 3.4 M positions is the same
    empty MXU tile as the forward's)."""
    h, w = x.shape[1], x.shape[2]
    xpad = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2), (0, 0)))
    gs = [g[..., o] for o in range(g.shape[-1])]

    def contract(window: jax.Array) -> jax.Array:
        return jnp.stack([
            jnp.stack([jnp.sum(window[..., c] * go) for go in gs])
            for c in range(x.shape[-1])
        ])

    return jnp.stack([
        jnp.stack([contract(xpad[:, ky : ky + h, kx : kx + w]) for kx in range(kw)])
        for ky in range(kh)
    ])


@jax.custom_vjp
def _conv_same(x: jax.Array, weight: jax.Array) -> jax.Array:
    """Stride-1 SAME convolution, NHWC x HWIO, odd kernel, few channels
    (:func:`tap_form`): the tap sum, with the two cotangents' own rule
    below."""
    return _conv_same_taps(x, weight)


def _conv_same_fwd(x, weight):
    return _conv_same_taps(x, weight), (x, weight)


def _conv_same_bwd(res, g):
    """Only ``(x, weight)`` are kept from the forward, never the k*k
    shifted windows.

    Input cotangent: the convolution's transpose, which for stride 1 and
    SAME padding is the same convolution of ``g`` with the kernel flipped
    in both window axes and its channel roles swapped: a tap sum again.

    Kernel cotangent: one multiply + reduce per tap
    (:func:`_kernel_cotangent`). XLA's own rule is a convolution of the
    input with the output cotangent as its window; for NCUP's planes (a
    full-resolution frame, 1-4 channels) that window is the whole frame,
    and the TPU compiler's code for it at float32 `highest` took over
    19 GB of host memory to compile for ONE 368x768 sample (PERF.md
    section 6, PR 26)."""
    x, weight = res
    kh, kw = weight.shape[0], weight.shape[1]
    dx = _conv_same_taps(g, weight[::-1, ::-1].transpose(0, 1, 3, 2))
    return dx, _kernel_cotangent(x, g, kh, kw).astype(weight.dtype)


_conv_same.defvjp(_conv_same_fwd, _conv_same_bwd)


def downsample_data_conf(
    data: jax.Array, conf: jax.Array, pooling_type: str = "conf_based"
) -> tuple[jax.Array, jax.Array]:
    """2x2 stride-2 confidence-aware downsampling.

    Max-pools the confidence and gathers data at the confidence argmax
    ('conf_based') or max-pools data directly ('max_pooling'); the pooled
    confidence is divided by 4 (the Jacobian of the scale change —
    reference: core/nconv_modules.py:94-104).

    Args:
      data, conf: (B, H, W, C) with H, W even.
    """
    B, H, W, C = conf.shape
    cb = conf.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 5, 2, 4)
    cb = cb.reshape(B, H // 2, W // 2, C, 4)
    conf_ds = cb.max(axis=-1) / 4.0
    if pooling_type == "conf_based":
        idx = cb.argmax(axis=-1)
        db = data.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 5, 2, 4)
        db = db.reshape(B, H // 2, W // 2, C, 4)
        data_ds = jnp.take_along_axis(db, idx[..., None], axis=-1)[..., 0]
    elif pooling_type == "max_pooling":
        db = data.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 5, 2, 4)
        data_ds = db.reshape(B, H // 2, W // 2, C, 4).max(axis=-1)
    else:
        raise ValueError(f"unknown pooling_type: {pooling_type!r}")
    return data_ds, conf_ds


def zero_stuff_upsample(x: jax.Array, scale_h: int, scale_w: int) -> jax.Array:
    """Scatter low-res samples into a zeroed high-res grid at stride
    centers: ``out[:, sH//2::sH, sW//2::sW] = x`` (reference:
    core/upsampler.py:179-210), written as a zero pad with interior
    padding: on the chip an elementwise pass, where an indexed
    ``.at[].set`` into zeros is a scatter (PERF.md section 6, PR 27).

    Args:
      x: (B, H, W, C).
    Returns:
      (B, H*scale_h, W*scale_w, C) zeros except at the stuffed positions.
    """
    lo_h, lo_w = scale_h // 2, scale_w // 2
    return jax.lax.pad(
        x, jnp.zeros((), x.dtype),
        (
            (0, 0, 0),
            (lo_h, scale_h - 1 - lo_h, scale_h - 1),
            (lo_w, scale_w - 1 - lo_w, scale_w - 1),
            (0, 0, 0),
        ),
    )
