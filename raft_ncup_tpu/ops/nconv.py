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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Trace-time dispatch tally for the fused-kernel path: callers that label a
# measurement "nconv=pallas" (bench.py) must be able to tell whether the
# fused kernel actually ran or every call silently fell back to XLA
# (ADVICE r3: a baseline pinned under '+nconv_pallas' that measured the
# XLA path would poison every later comparison).
_dispatch_counts = {"fused": 0, "fallback": 0}


def reset_dispatch_counts() -> None:
    _dispatch_counts["fused"] = 0
    _dispatch_counts["fallback"] = 0


def dispatch_counts() -> dict:
    """Copy of the {'fused', 'fallback'} tally since the last reset.
    Counts trace-time decisions (one per distinct nconv2d call site per
    TRACE), not runtime executions — extra traces in the same process
    (custom_vjp backward, retraces, concurrent threads) inflate the
    tally, so values are only interpretable between a reset and a single
    lowering in a single thread (bench.py's discipline)."""
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
      impl: 'xla' (two convs + divide) or 'pallas' (fused single-pass
        kernel, raft_ncup_tpu.ops.nconv_pallas) — default comes from env
        RAFT_NCUP_NCONV_IMPL ('xla' until hardware timing proves the
        kernel). Off the TPU 'pallas' runs the XLA composition with a
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

    def conv(x: jax.Array) -> jax.Array:
        if stride == 1 and groups == 1 and kh % 2 == 1 and kw % 2 == 1:
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


def _conv_same_xla(x: jax.Array, weight: jax.Array) -> jax.Array:
    kh, kw = weight.shape[0], weight.shape[1]
    return jax.lax.conv_general_dilated(
        x, weight, window_strides=(1, 1),
        padding=((kh // 2, kh // 2), (kw // 2, kw // 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@jax.custom_vjp
def _conv_same(x: jax.Array, weight: jax.Array) -> jax.Array:
    """Stride-1 SAME convolution, NHWC x HWIO, odd kernel: the forward
    (and the input's cotangent) is ``conv_general_dilated`` as before; the
    kernel's cotangent has its own rule below."""
    return _conv_same_xla(x, weight)


def _conv_same_fwd(x, weight):
    return _conv_same(x, weight), (x, weight)


def _conv_same_bwd(res, g):
    """Input cotangent: the convolution's own transpose. Kernel cotangent:
    one contraction per tap, ``dw[ky, kx] = sum_bhw xpad[b, h+ky, w+kx, :]
    (x) g[b, h, w, :]``. XLA's rule is a convolution of the input with the
    output cotangent as its window; for NCUP's planes (a full-resolution
    frame, 1-4 channels) that window is the whole frame, and the TPU
    compiler's code for it at float32 `highest` took over 19 GB of host
    memory to compile for ONE 368x768 sample (PERF.md section 6, PR 26)."""
    x, weight = res
    kh, kw = weight.shape[0], weight.shape[1]
    h, w = x.shape[1], x.shape[2]
    _, vjp_x = jax.vjp(lambda x_: _conv_same_xla(x_, weight), x)
    xpad = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2), (0, 0)))
    dw = jnp.stack([
        jnp.stack([
            jnp.einsum("bhwc,bhwo->co", xpad[:, ky : ky + h, kx : kx + w], g)
            for kx in range(kw)
        ])
        for ky in range(kh)
    ])
    return vjp_x(g)[0], dw.astype(weight.dtype)


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
    core/upsampler.py:179-210).

    Args:
      x: (B, H, W, C).
    Returns:
      (B, H*scale_h, W*scale_w, C) zeros except at the stuffed positions.
    """
    B, H, W, C = x.shape
    out = jnp.zeros((B, H * scale_h, W * scale_w, C), dtype=x.dtype)
    return out.at[:, scale_h // 2 :: scale_h, scale_w // 2 :: scale_w, :].set(x)
