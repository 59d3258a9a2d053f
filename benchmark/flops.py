"""Operations the algorithm needs, from shapes alone (a copy of the
arithmetic of ``raft_ncup_tpu/utils/flops.py``, which a hand count agrees
with; ISSUE 23). The benchmark keeps its own so that no later PR can move
the yardstick: one conv = 2*k*k*Cin*Cout*Hout*Wout (a multiply-add is 2),
elementwise and normalization work ignored (under 1% here), every refinement
iteration counted (XLA's ``cost_analysis()`` counts a ``scan`` body once and
is never used for a utilisation).

``model`` is the ``model`` section of a configuration file.
"""

from __future__ import annotations


def _conv(k: int, cin: int, cout: int, h: int, w: int) -> float:
    return 2.0 * k * k * cin * cout * h * w


def _basic_encoder(h: int, w: int, out_dim: int) -> float:
    f = _conv(7, 3, 64, h // 2, w // 2)
    f += 4 * _conv(3, 64, 64, h // 2, w // 2)
    h4, w4 = h // 4, w // 4
    f += _conv(3, 64, 96, h4, w4) + 3 * _conv(3, 96, 96, h4, w4) + _conv(1, 64, 96, h4, w4)
    h8, w8 = h // 8, w // 8
    f += _conv(3, 96, 128, h8, w8) + 3 * _conv(3, 128, 128, h8, w8) + _conv(1, 96, 128, h8, w8)
    return f + _conv(1, 128, out_dim, h8, w8)


def _update_block(h8: int, w8: int, corr_planes: int) -> float:
    f = _conv(1, corr_planes, 256, h8, w8) + _conv(3, 256, 192, h8, w8)
    f += _conv(7, 2, 128, h8, w8) + _conv(3, 128, 64, h8, w8)
    f += _conv(3, 192 + 64, 126, h8, w8)
    f += 6 * (2.0 * 5 * 384 * 128 * h8 * w8)  # SepConvGRU: six 1x5/5x1 convs on [h, x]
    return f + _conv(3, 128, 256, h8, w8) + _conv(3, 256, 2, h8, w8)


def _ncup(up: dict, h: int, w: int) -> float:
    """One NCUP x4 pass: the weights net at (H/4, W/4) on data(2)+guidance(128)
    channels, the NConv U-Net at full resolution once per flow channel; a
    normalized convolution is two convolutions."""
    chans = (130,) + tuple(up["weights_est_num_ch"]) + (2,)
    f = sum(
        _conv(k, cin, cout, h // 4, w // 4)
        for k, cin, cout in zip(up["weights_est_filter_sz"], chans[:-1], chans[1:])
    )
    m = up["channels_multiplier"]
    ke, kd, ko = up["encoder_filter_sz"], up["decoder_filter_sz"], up["out_filter_sz"]
    unet = 2 * _conv(ke, 1, m, h, w) + 2 * _conv(ke, m, m, h, w)
    unet += 2 * _conv(kd, 2 * m, m, h, w) + 2 * _conv(ko, m, 1, h, w)
    return f + 2 * unet


def forward_flops(model: dict, batch: int, height: int, width: int, iters: int,
                  upsample_every_iteration: bool = False) -> float:
    """One forward of ``model`` on ``batch`` pairs of (height, width) frames.
    In inference the upsampler runs once, after the loop; in training it
    runs in every iteration (``upsample_every_iteration``)."""
    h8, w8 = height // 8, width // 8
    levels, radius = model.get("corr_levels", 4), model.get("corr_radius", 4)
    planes = levels * (2 * radius + 1) ** 2
    f = 2 * _basic_encoder(height, width, 256) + _basic_encoder(height, width, 256)
    f += 2.0 * (h8 * w8) ** 2 * 256  # the all-pairs volume
    f += iters * _update_block(h8, w8, planes)
    heads = iters if upsample_every_iteration else 1
    if model["variant"] == "raft_nc_dbl":
        f += heads * _ncup(model["upsampler"], height, width)
    else:  # the mask head runs in every iteration of the update block
        f += iters * (_conv(3, 128, 256, h8, w8) + _conv(1, 256, 576, h8, w8))
    return batch * f
