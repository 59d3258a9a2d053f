"""TPU-target lowering of the Pallas kernels, validated WITHOUT a chip.

Interpret-mode equivalence (test_corr_pallas.py, test_nconv.py) proves
the math; these tests prove the kernels survive the Pallas -> Mosaic
MLIR conversion for a real TPU lowering target (`lowering_platforms=
("tpu",)` runs that conversion on any host) — the layer where dynamic
`pl.ds` slices, SMEM operands, and scratch shapes typically fail
(VERDICT r3 weak #4). One layer further down — the Mosaic -> TPU
binary compile, where every refusal of the first bring-up happened —
is tests/test_tpu_aot_compile.py; execution is chip_smoke.py.

Shapes mirror the real workloads: the Sintel fine-tune crop's 1/8-res
feature maps for the corr lookup, full-res 1-2 channel NCUP convs for
the fused NConv, and the 1080p mixed per-level dispatch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.ops import corr_pallas as cpk
from raft_ncup_tpu.ops.geometry import coords_grid
from raft_ncup_tpu.ops.nconv import positivity
from raft_ncup_tpu.ops.nconv_pallas import nconv2d_fused

@pytest.fixture(autouse=True)
def _pin_vmem_budget(monkeypatch):
    """The dispatch-count pins below are budget-sensitive (1080p level 1
    misses the default 16 MiB budget by ~1.3%), so test the gating logic
    against the default budget, not the ambient RAFT_NCUP_VMEM_BYTES
    override."""
    from raft_ncup_tpu.ops import nconv_pallas as npk

    monkeypatch.setattr(cpk, "_VMEM_BYTES", 16 * 1024 * 1024)
    monkeypatch.setattr(npk, "_VMEM_BYTES", 16 * 1024 * 1024)


def _lower_for_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)
    ).as_text()


def _count_mosaic_calls(text: str) -> int:
    return text.count("tpu_custom_call")


class TestCorrLowering:
    def test_training_crop_all_levels_lower(self):
        """368x768 crop -> 46x96 1/8-res fmaps, C=256: every pyramid
        level fits VMEM and must emit one Mosaic call."""
        B, H, W, C = 1, 46, 96, 256
        g = np.random.default_rng(0)
        f1 = jnp.asarray(g.normal(size=(B, H, W, C)), jnp.float32)
        f2 = jnp.asarray(g.normal(size=(B, H, W, C)), jnp.float32)
        coords = coords_grid(B, H, W)

        cpk.reset_dispatch_counts()
        text = _lower_for_tpu(
            lambda a, b, c: cpk.corr_lookup_pallas(a, b, c, 4, 4, False),
            f1, f2, coords,
        )
        counts = cpk.dispatch_counts()
        assert counts["kernel"] == 4 and counts["fallback"] == 0
        assert _count_mosaic_calls(text) == 4

    def test_1080p_mixed_dispatch_lowers(self):
        """1088x1920 -> 136x240 1/8-res: levels 0 AND 1 exceed the
        default VMEM RESIDENCY budget (level 1's 68x120 padded slab
        needs ~15.29 MB vs the 15.1 MB 0.9x budget) and now take the
        BANDED kernel — the correlation memory wall no longer demotes
        the two largest levels to XLA; levels 2-3 stay resident — and
        the stitched four-kernel graph lowers for a TPU target. Counts
        pinned exactly so a gating change can't pass vacuously."""
        B, H, W, C = 1, 136, 240, 256
        g = np.random.default_rng(1)
        f1 = jnp.asarray(g.normal(size=(B, H, W, C)), jnp.float32)
        f2 = jnp.asarray(g.normal(size=(B, H, W, C)), jnp.float32)
        coords = coords_grid(B, H, W)

        cpk.reset_dispatch_counts()
        text = _lower_for_tpu(
            lambda a, b, c: cpk.corr_lookup_pallas(a, b, c, 4, 4, False),
            f1, f2, coords,
        )
        counts = cpk.dispatch_counts()
        assert counts["kernel"] == 2 and counts["banded"] == 2
        assert counts["fallback"] == 0
        assert _count_mosaic_calls(text) == 4

    def test_4k_every_level_qualifies_for_a_kernel_tier(self):
        """The ISSUE-15 residency pin: at 4K (2176x3840 -> 272x480
        1/8-res, C=256) NO pyramid level is forced to the pure-XLA
        fallback by the VMEM budget — at f32 or bf16. Exact tier split
        pinned: f32 = 1 resident + 3 banded, bf16 = 2 + 2 (bf16 halves
        the slab, so one more level re-qualifies for residency)."""
        C = 256
        levels_4k = [(272, 480), (136, 240), (68, 120), (34, 60)]
        expect = {
            None: (1, 3),           # f32: resident, banded
            jnp.bfloat16: (2, 2),   # bf16
        }
        for dtype, (want_res, want_band) in expect.items():
            resident = banded = 0
            for h, w in levels_4k:
                if cpk.fits_vmem(h, w, C, 4, dtype=dtype):
                    resident += 1
                else:
                    plan = cpk.band_plan(h, w, C, 4, dtype=dtype)
                    assert plan is not None, (h, w, dtype)
                    band_rows, n_bands = plan
                    assert cpk._banded_vmem_bytes(
                        h, w, C, 4, band_rows,
                        itemsize=2 if dtype is not None else 4,
                    ) <= int(0.9 * cpk._VMEM_BYTES)
                    banded += 1
            assert (resident, banded) == (want_res, want_band), dtype

    def test_4k_dispatch_counts_pinned_at_trace_time(self):
        """Three-tier accounting at the 4K shape, pinned by an abstract
        trace (eval_shape — dispatch is a trace-time choice, no
        compile, no execution): f32 routes 1 level resident + 3 banded,
        0 fallback."""
        B, H, W, C = 1, 272, 480, 256
        f1 = jax.ShapeDtypeStruct((B, H, W, C), jnp.float32)
        f2 = jax.ShapeDtypeStruct((B, H, W, C), jnp.float32)
        cds = jax.ShapeDtypeStruct((B, H, W, 2), jnp.float32)

        cpk.reset_dispatch_counts()
        jax.eval_shape(
            lambda a, b, c: cpk.corr_lookup_pallas(a, b, c, 4, 4, False),
            f1, f2, cds,
        )
        counts = cpk.dispatch_counts()
        assert counts["kernel"] == 1 and counts["banded"] == 3
        assert counts["fallback"] == 0 and counts["levels_total"] == 4

    def test_gradient_graph_lowers(self):
        """The custom-VJP backward graph must lower for TPU too."""
        B, H, W, C = 1, 16, 24, 64
        g = np.random.default_rng(2)
        f1 = jnp.asarray(g.normal(size=(B, H, W, C)), jnp.float32)
        f2 = jnp.asarray(g.normal(size=(B, H, W, C)), jnp.float32)
        coords = coords_grid(B, H, W)

        def loss(a, b, c):
            return (cpk.corr_lookup_pallas(a, b, c, 4, 2, False) ** 2).sum()

        text = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), f1, f2, coords)
        assert text  # lowering itself is the assertion


class TestFullModelLowering:
    def test_flagship_forward_lowers_with_both_kernels(self, monkeypatch):
        """The integration the chip will actually run: the FULL flagship
        raft_nc_dbl forward, corr_impl='pallas' + nconv impl 'pallas',
        lowered for a TPU target with the kernels fused in (not
        interpret mode). Abstract init (eval_shape) + ShapeDtypeStruct
        args — nothing executes on the CPU host."""
        from raft_ncup_tpu.config import flagship_config
        from raft_ncup_tpu.models import get_model
        from raft_ncup_tpu.utils import runtime

        # The model and nconv2d gate Mosaic on the *current* backend;
        # pretend it is TPU-class so the lowered graph takes the real
        # kernel paths (interpret=False) rather than the interpreter.
        monkeypatch.setattr(runtime, "is_tpu_backend", lambda: True)
        monkeypatch.setenv("RAFT_NCUP_NCONV_IMPL", "pallas")

        model = get_model(
            flagship_config(dataset="sintel", corr_impl="pallas")
        )
        shape = (1, 96, 128, 3)
        variables = jax.eval_shape(
            lambda k: model.init(k, shape), jax.random.PRNGKey(0)
        )
        img = jax.ShapeDtypeStruct(shape, jnp.float32)

        def fwd(v, a, b):
            return model.apply(v, a, b, iters=2, test_mode=True)

        text = jax.jit(fwd).trace(variables, img, img).lower(
            lowering_platforms=("tpu",)
        ).as_text()
        # At 96x128 (12x16 1/8-res fmaps) every corr level fits VMEM and
        # the NCUP convs pass their gate: Mosaic calls must be present.
        assert _count_mosaic_calls(text) > 0


class TestNConvLowering:
    # Only shapes the dispatch gate actually routes to the kernel
    # (nconv_pallas.fits_vmem at the default 16 MiB budget): full-res
    # k=5/k=1 passes; the k=3 two-channel conv only fits at the UNet's
    # downsampled half resolution.
    @pytest.mark.parametrize("k,cin,cout,h,w", [
        (5, 1, 2, 368, 768),
        (3, 2, 2, 184, 384),
        (1, 2, 1, 368, 768),
    ])
    def test_flagship_shapes_lower(self, k, cin, cout, h, w):
        """NCUP convs at the shapes the gate dispatches to the kernel —
        the NConvUNet runs these 12x per forward."""
        from raft_ncup_tpu.ops.nconv_pallas import fits_vmem, supported

        assert supported((k, k, cin, cout), stride=1, groups=1)
        assert fits_vmem(h, w, cin, cout, k)
        g = np.random.default_rng(3)
        data = jnp.asarray(g.normal(size=(2, h, w, cin)), jnp.float32)
        conf = jnp.asarray(g.random((2, h, w, cin)), jnp.float32)
        wt = positivity(
            jnp.asarray(g.normal(size=(k, k, cin, cout)), jnp.float32)
        )
        b = jnp.asarray(g.normal(size=(cout,)), jnp.float32)
        text = _lower_for_tpu(
            lambda d, c, w, b: nconv2d_fused(d, c, w, b, 1e-20, False),
            data, conf, wt, b,
        )
        assert _count_mosaic_calls(text) == 1

    def test_gradient_graph_lowers(self):
        g = np.random.default_rng(4)
        data = jnp.asarray(g.normal(size=(1, 32, 48, 1)), jnp.float32)
        conf = jnp.asarray(g.random((1, 32, 48, 1)), jnp.float32)
        w = positivity(
            jnp.asarray(g.normal(size=(3, 3, 1, 2)), jnp.float32)
        )
        b = jnp.asarray(g.normal(size=(2,)), jnp.float32)

        def loss(d, c, w, b):
            out, co = nconv2d_fused(d, c, w, b, 1e-20, False)
            return (out ** 2).sum() + (co ** 2).sum()

        text = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2, 3)),
                              data, conf, w, b)
        assert text
