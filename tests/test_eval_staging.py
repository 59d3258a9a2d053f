"""Validation stages frames at the dataset's own width (PR 43): uint8 rows
to the device, the widening inside the metrics program.

(a) a pass over uint8 frames returns the accumulator of the same frames
handed as float32, bit for bit; (b) what ``_stage_batch`` hands the copy,
and the bytes a pair ``eval_input_bytes_total`` counts; (c) one
``ShapeCachedForward`` holds a uint8-fed and a float32-fed executable of
one shape, and the float32 one's module is the one it always was.
"""

from __future__ import annotations

import re

import jax
import numpy as np
import pytest

from raft_ncup_tpu.config import small_model_config
from raft_ncup_tpu.data.synthetic import SyntheticFlowDataset
from raft_ncup_tpu.evaluation import _run_metric_pass, _stage_batch
from raft_ncup_tpu.inference import metrics as metrics_mod
from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
from raft_ncup_tpu.models.raft import RAFT
from raft_ncup_tpu.observability import Telemetry
from raft_ncup_tpu.ops import InputPadder

NATIVE_HW = (36, 44)  # pads to (40, 48) under "sintel" and "kitti"
ALIGNED_HW = (40, 48)


class _Samples:
    """A synthetic split held in memory, its frames uint8 (as every
    dataset hands them) or the same values widened to float32."""

    def __init__(self, hw, n: int, frames, with_valid: bool = False):
        base = SyntheticFlowDataset(hw, length=n, seed=11, style="smooth")
        self._samples = []
        for i in range(n):
            s = dict(base.sample(i))
            assert s["image1"].dtype == np.uint8
            s["image1"] = s["image1"].astype(frames)
            s["image2"] = s["image2"].astype(frames)
            if with_valid:
                valid = np.ones(hw, np.float32)
                valid[: hw[0] // 2 if i % 2 else 0] = 0.0
                s["valid"] = valid
            self._samples.append(s)

    def __len__(self):
        return len(self._samples)

    def sample(self, index):
        return self._samples[index]


@pytest.fixture(scope="module", params=["raft", "raft_nc_dbl"])
def tiny(request):
    model = RAFT(small_model_config(request.param, dataset="sintel"))
    return model, model.init(jax.random.PRNGKey(0), (1, *ALIGNED_HW, 3))


# --------------------------------------------------- (a) the same sums


@pytest.mark.parametrize(
    "hw,kind,pad_mode,with_valid",
    [
        (NATIVE_HW, "px", "sintel", False),
        (ALIGNED_HW, "epe", None, False),
        (NATIVE_HW, "kitti", "kitti", True),
    ],
    ids=["sintel", "unpadded", "kitti_valid"],
)
def test_uint8_frames_give_the_float32_frames_sums(
    tiny, hw, kind, pad_mode, with_valid
):
    model, variables = tiny
    fwd = ShapeCachedForward(model, variables)
    accs = {}
    for frames in (np.uint8, np.float32):
        accs[frames] = _run_metric_pass(
            fwd, _Samples(hw, 4, frames, with_valid), kind=kind, iters=1,
            batch_size=2, pad_mode=pad_mode, with_valid=with_valid,
            num_workers=2, telemetry=Telemetry(),
        )
    np.testing.assert_array_equal(accs[np.uint8], accs[np.float32])
    assert np.isfinite(accs[np.uint8]).all() and accs[np.uint8][1] > 0
    # one executable a width, each serving its pass's second batch
    assert fwd.stats == {"compiles": 2, "hits": 2, "evictions": 0}


# ------------------------------------------- (b) what the copy is handed


def _bytes_a_pair(native_hw, padded_hw, frame_bytes: int) -> int:
    (h, w), (ph, pw) = native_hw, padded_hw
    return 2 * ph * pw * 3 * frame_bytes + h * w * 2 * 4


@pytest.mark.parametrize(
    "native,padded,narrow,wide",
    [
        # the stated bytes a pair of the benchmark's two shapes: 6.27 MB
        # (14.4 as float32 rows) and 29.0 MB (66.4); shapes alone
        ((436, 1024), (440, 1024), 6_275_072, 14_385_152),
        ((1080, 1920), (1080, 1920), 29_030_400, 66_355_200),
    ],
)
def test_bytes_a_pair_of_the_cells_shapes(native, padded, narrow, wide):
    (t, b), (l, r) = InputPadder((1, *native, 3), mode="sintel").pad_spec
    assert (native[0] + t + b, native[1] + l + r) == padded
    assert _bytes_a_pair(native, padded, 1) == narrow
    assert _bytes_a_pair(native, padded, 4) == wide


@pytest.mark.parametrize("frames", [np.uint8, np.float32, np.float64])
def test_staged_batch_keeps_the_datasets_width(frames):
    ds = _Samples(NATIVE_HW, 2, frames, with_valid=True)
    group = [ds.sample(0), ds.sample(1)]
    arrays, pad = _stage_batch(group, pad_mode="sintel", with_valid=True)
    want = np.uint8 if frames == np.uint8 else np.float32
    padder = InputPadder((2, *NATIVE_HW, 3), mode="sintel")
    assert pad == padder.pad_spec and pad != ((0, 0), (0, 0))
    (t, b), (l, r) = pad
    for key in ("image1", "image2"):
        got = arrays[key]
        assert got.dtype == want and got.shape == (2, *ALIGNED_HW, 3)
        native = np.stack([s[key] for s in group])
        inner = got[:, t:got.shape[1] - b, l:got.shape[2] - r]
        np.testing.assert_array_equal(inner, native)
        # replicated edges: every padded row / column is its neighbour's
        np.testing.assert_array_equal(
            got, np.asarray(padder.pad(native.astype(np.float32))[0])
        )
    assert arrays["flow"].dtype == arrays["valid"].dtype == np.float32
    assert arrays["flow"].shape == (2, *NATIVE_HW, 2)
    np.testing.assert_array_equal(arrays["flow"][1], group[1]["flow"])
    np.testing.assert_array_equal(arrays["valid"][1], group[1]["valid"])


def test_one_float_frame_widens_its_whole_batch():
    ds = _Samples(ALIGNED_HW, 2, np.uint8)
    odd = dict(ds.sample(1), image2=ds.sample(1)["image2"].astype(np.float32))
    arrays, pad = _stage_batch([ds.sample(0), odd])
    assert pad is None
    assert arrays["image1"].dtype == arrays["image2"].dtype == np.float32
    np.testing.assert_array_equal(arrays["image1"][0], ds.sample(0)["image1"])


@pytest.mark.parametrize(
    "frames,frame_bytes", [(np.uint8, 1), (np.float32, 4)]
)
def test_the_counter_reads_the_width_the_frames_went_at(frames, frame_bytes):
    from tests.test_inference_pipeline import _DummyModel

    tel = Telemetry()
    fwd = ShapeCachedForward(_DummyModel(), {}, telemetry=tel)
    n = 5
    _run_metric_pass(
        fwd, _Samples(NATIVE_HW, n, frames), kind="px", iters=1,
        batch_size=2, pad_mode="sintel", num_workers=2, telemetry=tel,
    )
    assert tel.counter_value("eval_pairs_total") == n
    assert tel.counter_value("eval_input_bytes_total") == n * _bytes_a_pair(
        NATIVE_HW, ALIGNED_HW, frame_bytes
    )


# ------------------------------- (c) two executables of one shape, one old


def _metrics_batch(frames):
    g = np.random.default_rng(5)
    return {
        "image1": g.integers(0, 256, (1, *ALIGNED_HW, 3)).astype(frames),
        "image2": g.integers(0, 256, (1, *ALIGNED_HW, 3)).astype(frames),
        "flow": g.normal(size=(1, *ALIGNED_HW, 2)).astype(np.float32),
    }


def test_one_cache_serves_both_widths_and_float32_keeps_its_module(tiny):
    model, variables = tiny
    acc = metrics_mod.init_acc("epe")
    both = ShapeCachedForward(model, variables)
    narrow = both.metrics(_metrics_batch(np.uint8), iters=1, acc=acc, kind="epe")
    hlo_u8 = both.lowered_hlo()
    wide = both.metrics(_metrics_batch(np.float32), iters=1, acc=acc, kind="epe")
    hlo_f32 = both.lowered_hlo()
    assert both.stats == {"compiles": 2, "hits": 0, "evictions": 0}
    np.testing.assert_array_equal(np.asarray(narrow), np.asarray(wide))
    # either again: served, not built
    both.metrics(_metrics_batch(np.uint8), iters=1, acc=acc, kind="epe")
    both.metrics(_metrics_batch(np.float32), iters=1, acc=acc, kind="epe")
    assert both.stats == {"compiles": 2, "hits": 2, "evictions": 0}
    frames = [
        e["meta"]["frames"]
        for e in map(both.costs.entry, both.costs.keys())
        if e["meta"].get("kind") == "metrics" and "frames" in e["meta"]
    ]
    assert ("uint8", "uint8") in frames and ("float32", "float32") in frames

    # a cache that never saw a uint8 frame lowers the same float32 module
    alone = ShapeCachedForward(model, variables)
    batch = _metrics_batch(np.float32)
    alone.metrics(batch, iters=1, acc=acc, kind="epe")
    assert _instructions(alone.lowered_hlo()) == _instructions(hlo_f32)

    # ... which is, instruction for instruction, the module of the program
    # as it was before any cast stood in it
    def fn(v, i1, i2, extra, acc_in):
        def head(flow_up):
            return metrics_mod.accumulate(
                "epe", acc_in, flow_up, extra["flow"], valid=None, band=None,
                pad=None,
            )

        return model.apply(
            v, i1, i2, iters=1, test_mode=True, mesh=None, metric_head=head
        )[1]

    lowered = jax.jit(fn).lower(
        variables, batch["image1"], batch["image2"], {"flow": batch["flow"]}, acc
    )
    castless = lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string()
    assert _instructions(castless) == _instructions(hlo_f32)

    # the uint8 module: two u8 frame parameters, each widened once, and
    # nothing else of it differs in what it converts
    u8 = [ln for ln in hlo_u8.splitlines() if " parameter(" in ln and "u8[" in ln]
    assert len(u8) == 2 and all("u8[1,40,48,3]" in ln for ln in u8)
    assert "u8[" not in hlo_f32
    assert hlo_u8.count(" convert(") == hlo_f32.count(" convert(") + 2


def _instructions(hlo: str) -> list:
    """An HLO module's computations without what names the python that
    traced them (the tables of files and lines, each ``metadata``)."""
    return [
        re.sub(r", metadata=\{[^}]*\}", "", line)
        for line in hlo.splitlines()
        if " = " in line or line.startswith(("ENTRY", "%", "}"))
    ]
