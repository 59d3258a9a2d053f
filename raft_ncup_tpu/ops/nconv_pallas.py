"""Pallas TPU kernel: fused normalized convolution (SURVEY.md §2a(b)).

The XLA path (raft_ncup_tpu.ops.nconv.nconv2d) computes two convolutions —
``conv(conf * data)`` and ``conv(conf)`` — plus a divide and a scale
(reference semantics: core/nconv_modules.py:164-199). NCUP's convolutions
are pathological for the MXU: 1-4 channels at FULL image resolution (the
arithmetic fills ~1% of a tile), once per inference forward and twelve
times per training step at 368x768. They are shift-and-accumulate
stencils, not matmuls, and since PR 27 the XLA path computes them as such
(float32 tap sums on the vector units, forward and both cotangents). Timed
on a v5e (PERF.md section 6, PR 27): one NCUP forward through this kernel
4.35 ms at 12 x 368x768 and 10.88 ms at 16 x 440x1024, through the XLA tap
sums 3.28 and 8.31 (through MXU convolutions 66.7 and 128.5). The kernel
stays an option (``RAFT_NCUP_NCONV_IMPL=pallas``), off the default path.

This kernel computes the whole NConv2d in ONE pass, as an unrolled
shift-multiply-accumulate over ROW TILES of the image plane:

- Both operands (``conf``, ``data*conf``) are zero-padded outside the
  kernel and stay in HBM; each ``(batch, row tile)`` program DMAs its
  strip — ``TILE_ROWS`` output rows plus an 8-row halo, an aligned
  window of the sublane-tiled row dimension — into a VMEM scratch.
  Every kernel tap is then a STATIC slice of the strip (conv tap offsets
  are compile-time constants), so the inner loop is pure (8, 128)-tiled
  VPU work on accumulators small enough to live in vector registers —
  no gathers, no MXU channel padding waste. (The first version blocked
  the WHOLE image per program: Mosaic materialised every shifted
  whole-image temporary in VMEM and refused 184x384 at 22.9 MB against
  the 16 MiB scoped limit, and 368x768 did not finish compiling.)
- The divide, bias, and confidence propagation (``conv(conf)/sum(w)``)
  fuse into the same pass, so HBM traffic is one read of each operand
  (plus the halo rows) and one write of each output — the fusion XLA is
  not guaranteed to find across the conv/divide boundary.

Supported surface = exactly what NCUP uses (stride 1, groups 1, odd
square kernels up to 9, SAME padding); anything else — or a row so wide
that a strip overflows the VMEM budget — takes the XLA composition, per
shape, at trace time. :func:`fits_vmem` counts what Mosaic allocates, so
every admitted shape compiles (tests/test_tpu_aot_compile.py asks the
chip's compiler).

Forward-only; ``nconv2d_fused`` wraps the kernel in ``jax.custom_vjp``
whose backward differentiates the XLA composition (same values =>
correct gradients), keeping the op trainable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_ncup_tpu.utils.runtime import VMEM_BYTES as _VMEM_BYTES

# Output rows per program. (16, W) f32 accumulators are 2 vregs per 128
# lanes, so both stay register-resident up to W ~ 1k and spill gently
# beyond; a multiple of the 8-row sublane tile so every strip DMA starts
# tile-aligned.
TILE_ROWS = 16
# Rows a strip extends past its tile: >= k - 1 for every supported k, and
# a multiple of 8 so the strip length is too.
HALO_ROWS = 8


def _tile(n: int, t: int) -> int:
    return -(-n // t) * t


def vmem_bytes(h: int, w: int, cin: int, cout: int, k: int) -> int:
    """Bytes of VMEM Mosaic allocates for one program, counted the way it
    allocates them: f32, the last two dims of every buffer padded to the
    (8, 128) tile, the two strip scratches single-buffered (manual DMA),
    the two pipelined output blocks double-buffered, plus the
    accumulators and one shifted strip per operand as spill room."""
    del h  # row-tiled: the footprint does not depend on the image height
    strip = cin * (TILE_ROWS + HALO_ROWS) * _tile(w + k - 1, 128)
    out_block = cout * TILE_ROWS * _tile(w, 128)
    spill = 4 * TILE_ROWS * _tile(w + k - 1, 128)
    return 4 * (2 * strip + 2 * 2 * out_block + spill)


def fits_vmem(h: int, w: int, cin: int, cout: int, k: int) -> bool:
    """Whether one program's working set (:func:`vmem_bytes`) fits the
    scoped-VMEM budget. The 3/8 share is calibrated, not derived: it is
    what the chip's compiler was asked and accepted (a 3840-wide 4 -> 2
    channel 3x3 plane counts 5.75 MiB and compiles; an 8192-wide row
    counts 7.6 MiB and is refused), so the gate admits nothing beyond
    what has been seen to compile."""
    return vmem_bytes(h, w, cin, cout, k) <= int(0.375 * _VMEM_BYTES)


# The kernel body unrolls cout * k * k * cin Python loop iterations
# (one vector FMA each). NCUP's nconvs are 1-2 channels (5x5x2x2 = 100
# iterations); past a few hundred the unrolled Mosaic program blows up
# compile time and VMEM register pressure, so cap it and let XLA take
# those shapes.
MAX_UNROLL = 256


def supported(weight_shape, stride: int, groups: int) -> bool:
    kh, kw, cin, cout = (
        weight_shape[0], weight_shape[1], weight_shape[2], weight_shape[3],
    )
    return (
        kh == kw
        and kh % 2 == 1
        and kh - 1 <= HALO_ROWS
        and stride == 1
        and groups == 1
        and kh * kw * cin * cout <= MAX_UNROLL
    )


def _kernel(dc_hbm, c_hbm, w_ref, wsum_ref, bias_ref, out_ref, cout_ref,
            dc_buf, c_buf, sem, *, k: int, cin: int, cout: int, eps: float):
    """One (batch element, row tile), channel-FIRST so the (H, W) image
    plane rides the (sublane, lane) vector tiles — channels-last with
    Cin/Cout of 1-2 would waste 126/128 lanes.

    dc_hbm/c_hbm: (B, Cin, Hp, Wp) padded data*conf and conf, in HBM;
    w_ref: (k*k*Cin*Cout,) flat HWIO weights, wsum_ref/bias_ref: (Cout,)
    — scalars, SMEM; outputs: (Cout, TILE_ROWS, W) blocks;
    dc_buf/c_buf: (Cin, TILE_ROWS + HALO_ROWS, Wp) VMEM strips, Wp a
    multiple of 128."""
    b = pl.program_id(0)
    row0 = pl.multiple_of(pl.program_id(1) * TILE_ROWS, 8)
    rows = pl.ds(row0, TILE_ROWS + HALO_ROWS)
    copies = [
        pltpu.make_async_copy(hbm.at[b, :, rows, :], buf, sem.at[i])
        for i, (hbm, buf) in enumerate(((dc_hbm, dc_buf), (c_hbm, c_buf)))
    ]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()
    T, W = out_ref.shape[1], out_ref.shape[2]
    for co in range(cout):
        acc_x = jnp.zeros((T, W), jnp.float32)
        acc_c = jnp.zeros((T, W), jnp.float32)
        for ky in range(k):
            for kx in range(k):
                for ci in range(cin):
                    w = w_ref[((ky * k + kx) * cin + ci) * cout + co]
                    acc_x += w * dc_buf[ci, ky : ky + T, kx : kx + W]
                    acc_c += w * c_buf[ci, ky : ky + T, kx : kx + W]
        out_ref[co] = acc_x / (acc_c + eps) + bias_ref[co]
        cout_ref[co] = acc_c / wsum_ref[co]


def _forward(data, conf, weight, bias, eps, interpret):
    B, H, W, Cin = data.shape
    k = weight.shape[0]
    Cout = weight.shape[-1]
    p = k // 2
    f32 = jnp.float32
    n_tiles = -(-H // TILE_ROWS)
    Ht = n_tiles * TILE_ROWS
    # NHWC -> NCHW, pad the image plane: the conv's own SAME margin, plus
    # zero rows below so the last tile's strip (tile + halo) is in-bounds
    # and zero columns right so a strip is whole 128-lane tiles (a DMA
    # slice must be).
    Wp = _tile(W + 2 * p, 128)
    pad = ((0, 0), (0, 0), (p, Ht + HALO_ROWS - H - p), (p, Wp - W - p))
    dc = jnp.pad((data * conf).astype(f32).transpose(0, 3, 1, 2), pad)
    cp = jnp.pad(conf.astype(f32).transpose(0, 3, 1, 2), pad)
    wsum = weight.sum(axis=(0, 1, 2)).astype(f32)
    b = bias.astype(f32) if bias is not None else jnp.zeros((Cout,), f32)
    # Scalars the unrolled taps read live in SMEM; the CPU interpreter
    # keeps the default space.
    scalars = pl.BlockSpec(
        **({} if interpret else {"memory_space": pltpu.SMEM})
    )
    out_block = pl.BlockSpec(
        (None, Cout, TILE_ROWS, W), lambda b, r: (b, 0, r, 0)
    )

    out, conf_out = pl.pallas_call(
        functools.partial(_kernel, k=k, cin=Cin, cout=Cout, eps=eps),
        grid=(B, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            scalars,
            scalars,
            scalars,
        ],
        out_specs=[out_block, out_block],
        out_shape=[
            jax.ShapeDtypeStruct((B, Cout, Ht, W), f32),
            jax.ShapeDtypeStruct((B, Cout, Ht, W), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Cin, TILE_ROWS + HALO_ROWS, Wp), f32),
            pltpu.VMEM((Cin, TILE_ROWS + HALO_ROWS, Wp), f32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        name="nconv2d_fused",
    )(dc, cp, weight.astype(f32).reshape(-1), wsum, b)
    # NCHW -> NHWC; restore the input dtype so flipping impl never
    # changes the op's output dtype (the XLA path preserves it).
    out = out[:, :, :H].transpose(0, 2, 3, 1).astype(data.dtype)
    conf_out = conf_out[:, :, :H].transpose(0, 2, 3, 1).astype(conf.dtype)
    return out, conf_out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def nconv2d_fused(data, conf, weight, bias, eps: float = 1e-20,
                  interpret: bool = False):
    """Fused NConv2d forward: returns ``(out, conf_out)`` equivalent to
    the XLA composition in :func:`raft_ncup_tpu.ops.nconv.nconv2d`
    (stride 1, groups 1, odd square kernel) up to float associativity.

    ``bias`` may be None. Caller is responsible for gating via
    :func:`supported` and :func:`fits_vmem`.
    """
    return _forward(data, conf, weight, bias, eps, interpret)


def _reference(data, conf, weight, bias, eps):
    from raft_ncup_tpu.ops.nconv import nconv2d

    # impl='xla' explicitly: with RAFT_NCUP_NCONV_IMPL=pallas exported the
    # env default would re-dispatch straight back to the fused kernel and
    # the backward would recurse without a base case.
    return nconv2d(data, conf, weight, bias, eps=eps, impl="xla")


def _fwd(data, conf, weight, bias, eps, interpret):
    out = _forward(data, conf, weight, bias, eps, interpret)
    return out, (data, conf, weight, bias)


def _bwd(eps, interpret, res, g):
    data, conf, weight, bias = res
    if bias is None:
        _, vjp = jax.vjp(
            lambda d, c, w: _reference(d, c, w, None, eps), data, conf, weight
        )
        gd, gc, gw = vjp(g)
        return gd, gc, gw, None
    _, vjp = jax.vjp(
        lambda d, c, w, b: _reference(d, c, w, b, eps), data, conf, weight, bias
    )
    return vjp(g)


nconv2d_fused.defvjp(_fwd, _bwd)
