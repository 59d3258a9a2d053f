"""Equivalence tests for the Pallas corr-lookup kernel (interpret mode on
CPU; the same kernel compiles for TPU)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_ncup_tpu.ops.corr import build_corr_pyramid, corr_lookup
from raft_ncup_tpu.ops.corr_pallas import corr_lookup_pallas
from raft_ncup_tpu.ops.geometry import coords_grid

B, H, W, C = 2, 8, 12, 16
RADIUS = 3
LEVELS = 3  # deepest level is 2x3 — exercises tiny-volume handling


def setup():
    g = np.random.default_rng(0)
    fmap1 = jnp.asarray(g.normal(size=(B, H, W, C)), jnp.float32)
    fmap2 = jnp.asarray(g.normal(size=(B, H, W, C)), jnp.float32)
    return fmap1, fmap2


class TestPallasLookup:
    def test_matches_volume_path_on_grid(self):
        fmap1, fmap2 = setup()
        coords = coords_grid(B, H, W)
        ref = corr_lookup(
            build_corr_pyramid(fmap1, fmap2, LEVELS), coords, RADIUS
        )
        out = corr_lookup_pallas(
            fmap1, fmap2, coords, RADIUS, LEVELS, True
        )
        assert out.shape == ref.shape
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_matches_volume_path_fractional_and_oob(self):
        fmap1, fmap2 = setup()
        g = np.random.default_rng(1)
        # Fractional offsets plus large displacements that push whole
        # windows out of bounds in every direction.
        coords = coords_grid(B, H, W) + jnp.asarray(
            g.uniform(-1.5 * max(H, W), 1.5 * max(H, W), (B, H, W, 2)),
            jnp.float32,
        ) * jnp.asarray(g.random((B, H, W, 2)) < 0.3, jnp.float32) + jnp.asarray(
            g.uniform(-0.99, 0.99, (B, H, W, 2)), jnp.float32
        )
        ref = corr_lookup(
            build_corr_pyramid(fmap1, fmap2, LEVELS), coords, RADIUS
        )
        out = corr_lookup_pallas(
            fmap1, fmap2, coords, RADIUS, LEVELS, True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_gradients_match_xla_path(self):
        fmap1, fmap2 = setup()
        coords = coords_grid(B, H, W) + 0.3

        def loss_pallas(f1, f2, c):
            return (
                corr_lookup_pallas(f1, f2, c, RADIUS, LEVELS, True) ** 2
            ).sum()

        def loss_ref(f1, f2, c):
            pyr = build_corr_pyramid(f1, f2, LEVELS)
            return (corr_lookup(pyr, c, RADIUS) ** 2).sum()

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(fmap1, fmap2, coords)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(fmap1, fmap2, coords)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
            )

    def test_query_count_not_multiple_of_group(self):
        """Adversarial (VERDICT r3 #3): H*W = 35 queries, not a multiple
        of the kernel's group-of-8 tiling — the tail group must still
        match the volume path exactly."""
        h, w = 5, 7
        g = np.random.default_rng(3)
        fmap1 = jnp.asarray(g.normal(size=(1, h, w, C)), jnp.float32)
        fmap2 = jnp.asarray(g.normal(size=(1, h, w, C)), jnp.float32)
        coords = coords_grid(1, h, w) + jnp.asarray(
            g.uniform(-2.0, 2.0, (1, h, w, 2)), jnp.float32
        )
        ref = corr_lookup(
            build_corr_pyramid(fmap1, fmap2, 2), coords, RADIUS
        )
        out = corr_lookup_pallas(fmap1, fmap2, coords, RADIUS, 2, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_every_window_fully_out_of_bounds(self):
        """Adversarial: displacements larger than the image in all four
        directions — every tap of every window is OOB, output must be
        exactly the reference's (zeros), no clamping artifacts."""
        fmap1, fmap2 = setup()
        big = 4.0 * max(H, W)
        for dx, dy in ((big, 0.0), (-big, 0.0), (0.0, big), (-big, -big)):
            coords = coords_grid(B, H, W) + jnp.asarray(
                [dx, dy], jnp.float32
            )
            ref = corr_lookup(
                build_corr_pyramid(fmap1, fmap2, LEVELS), coords, RADIUS
            )
            out = corr_lookup_pallas(
                fmap1, fmap2, coords, RADIUS, LEVELS, True
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
            )

    def test_mixed_level_dispatch_matches(self, monkeypatch):
        """Adversarial: a VMEM budget that rejects level 0's RESIDENT
        tier but accepts deeper levels (the 1080p dispatch boundary) —
        under the three-tier dispatch the rejected level lands on the
        BANDED kernel, not XLA, and the stitched banded+resident output
        must equal the pure XLA path."""
        from raft_ncup_tpu.ops import corr_pallas as cpk

        fmap1, fmap2 = setup()
        coords = coords_grid(B, H, W) + 0.25
        ref = corr_lookup(
            build_corr_pyramid(fmap1, fmap2, LEVELS), coords, RADIUS
        )
        level0_bytes = cpk._level_vmem_bytes(H, W, C, RADIUS)
        dispatched = []

        def fits(h, w, c, radius=4, dtype=None):
            ok = cpk._level_vmem_bytes(h, w, c, radius) < level0_bytes
            dispatched.append(((h, w), ok))
            return ok

        monkeypatch.setattr(cpk, "fits_vmem", fits)
        cpk.reset_dispatch_counts()
        out = corr_lookup_pallas(fmap1, fmap2, coords, RADIUS, LEVELS, True)
        # Level 0 missed residency and went BANDED, at least one deeper
        # level took the resident kernel, nothing fell back to XLA —
        # and the module tally (bench.py's honesty signal) agrees.
        assert dispatched[0][1] is False
        assert any(ok for _, ok in dispatched[1:])
        counts = cpk.dispatch_counts()
        assert counts["levels_total"] == LEVELS
        assert counts["banded"] >= 1 and counts["kernel"] >= 1
        assert counts["fallback"] == 0
        assert counts["kernel"] + counts["banded"] == LEVELS
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_all_levels_fallback_warns(self, monkeypatch):
        """ADVICE r3: when BOTH kernel tiers (resident fits_vmem and
        band_plan) reject every level, the 'pallas' label silently
        measures XLA — a warning must say so."""
        from raft_ncup_tpu.ops import corr_pallas as cpk

        fmap1, fmap2 = setup()
        coords = coords_grid(B, H, W)
        monkeypatch.setattr(cpk, "fits_vmem", lambda *a, **k: False)
        monkeypatch.setattr(cpk, "band_plan", lambda *a, **k: None)
        with pytest.warns(UserWarning, match="onthefly fallback for every"):
            cpk.corr_lookup_pallas(fmap1, fmap2, coords, RADIUS, LEVELS, True)

    def test_all_levels_fallback_raises_on_tpu(self, monkeypatch):
        """On the chip the same zero-kernel dispatch is an error: the
        'pallas' label must not measure XLA there."""
        from raft_ncup_tpu.ops import corr_pallas as cpk
        from raft_ncup_tpu.utils import runtime

        fmap1, fmap2 = setup()
        coords = coords_grid(B, H, W)
        monkeypatch.setattr(runtime, "is_tpu_backend", lambda: True)
        monkeypatch.setattr(cpk, "fits_vmem", lambda *a, **k: False)
        monkeypatch.setattr(cpk, "band_plan", lambda *a, **k: None)
        with pytest.raises(RuntimeError, match="corr_impl='onthefly'"):
            cpk.corr_lookup_pallas(fmap1, fmap2, coords, RADIUS, LEVELS, True)

    def test_banded_tier_dispatch_matches_onthefly(self, monkeypatch):
        """The full op with residency rejected everywhere: every level
        must land on the BANDED tier (counts pinned) and the output
        must match the XLA onthefly path."""
        from raft_ncup_tpu.ops import corr_pallas as cpk
        from raft_ncup_tpu.ops.corr import corr_lookup_onthefly

        fmap1, fmap2 = setup()
        g = np.random.default_rng(7)
        coords = coords_grid(B, H, W) + jnp.asarray(
            g.uniform(-5, 5, (B, H, W, 2)), jnp.float32
        )
        ref = corr_lookup_onthefly(fmap1, fmap2, coords, RADIUS, LEVELS)
        monkeypatch.setattr(cpk, "fits_vmem", lambda *a, **k: False)
        monkeypatch.setattr(cpk, "band_plan", lambda *a, **k: (3, 4))
        cpk.reset_dispatch_counts()
        out = cpk.corr_lookup_pallas(
            fmap1, fmap2, coords, RADIUS, LEVELS, True
        )
        counts = cpk.dispatch_counts()
        assert counts["banded"] == LEVELS
        assert counts["kernel"] == 0 and counts["fallback"] == 0
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_model_runs_with_pallas_impl(self):
        # On a non-TPU backend the model selects interpret mode itself
        # (models/raft.py), so corr_impl='pallas' works unpatched.
        from raft_ncup_tpu.config import small_model_config
        from raft_ncup_tpu.models.raft import RAFT

        cfg = small_model_config(
            "raft", dataset="chairs", corr_impl="pallas"
        )
        model = RAFT(cfg)
        shape = (1, 32, 48, 3)
        variables = model.init(jax.random.PRNGKey(0), shape)
        img = jnp.zeros(shape, jnp.float32)
        lr, up = model.apply(variables, img, img, iters=2, test_mode=True)
        assert up.shape == (1, 32, 48, 2)
        assert np.isfinite(np.asarray(up)).all()


class TestBandedLookup:
    """The banded tier in isolation (ops/corr_pallas.py "Banded tier"):
    level slabs stay in HBM, one band slab + halo is DMA'd per band,
    queries ride a stable argsort-by-band with a masked group loop.

    Parity contracts: BITWISE equality with the resident kernel (same
    per-query math, only regrouped — interpret mode, so bitwise means
    bitwise), and tolerance equality with the XLA onthefly path (a
    different but mathematically equal reduction order). Fully-OOB
    windows are exact zeros on every path, so THAT case is pinned
    bitwise against onthefly too.
    """

    def _run(self, fn, fmap1, fmap2, coords, levels, band_rows=3,
             qblk=16, radius=RADIUS):
        import math

        from raft_ncup_tpu.ops.corr import _pool_fmap_pyramid

        b, h, w, c = fmap1.shape
        f1 = fmap1.reshape(b, h * w, c) * (1.0 / math.sqrt(c))
        cflat = coords.astype(jnp.float32).reshape(b, h * w, 2)
        k2 = (2 * radius + 1) ** 2
        outs = []
        for lvl, f2l in enumerate(_pool_fmap_pyramid(fmap2, levels)):
            outs.append(fn(f1, f2l, cflat, lvl, band_rows, qblk))
        return jnp.concatenate(outs, -1).reshape(b, h, w, levels * k2)

    def _banded(self, fmap1, fmap2, coords, levels, band_rows=3, qblk=16):
        from raft_ncup_tpu.ops import corr_pallas as cpk

        return self._run(
            lambda f1, f2l, cf, lvl, br, qb: cpk._banded_lookup_one_level(
                f1, cpk._pad_level(f2l, RADIUS, br), f2l.shape[1:3], cf,
                RADIUS, lvl, band_rows=br, interpret=True, query_block=qb,
            ),
            fmap1, fmap2, coords, levels, band_rows, qblk,
        )

    def _resident(self, fmap1, fmap2, coords, levels, qblk=16):
        from raft_ncup_tpu.ops import corr_pallas as cpk

        return self._run(
            lambda f1, f2l, cf, lvl, br, qb: cpk._lookup_one_level(
                f1, cpk._pad_level(f2l, RADIUS), f2l.shape[1:3], cf, RADIUS,
                lvl, interpret=True, query_block=qb,
            ),
            fmap1, fmap2, coords, levels,
        )

    def test_bitwise_vs_resident_kernel(self):
        """Fractional + OOB displacements: the banded kernel must be
        BITWISE the resident kernel — banding regroups the same f32
        math, it must not change a single ulp."""
        fmap1, fmap2 = setup()
        g = np.random.default_rng(11)
        coords = coords_grid(B, H, W) + jnp.asarray(
            g.uniform(-1.5 * max(H, W), 1.5 * max(H, W), (B, H, W, 2)),
            jnp.float32,
        ) * jnp.asarray(
            g.random((B, H, W, 2)) < 0.3, jnp.float32
        ) + jnp.asarray(g.uniform(-0.99, 0.99, (B, H, W, 2)), jnp.float32)
        banded = self._banded(fmap1, fmap2, coords, LEVELS)
        resident = self._resident(fmap1, fmap2, coords, LEVELS)
        assert np.array_equal(np.asarray(banded), np.asarray(resident))

    def test_parity_vs_onthefly(self):
        from raft_ncup_tpu.ops.corr import corr_lookup_onthefly

        fmap1, fmap2 = setup()
        g = np.random.default_rng(12)
        coords = coords_grid(B, H, W) + jnp.asarray(
            g.uniform(-4, 4, (B, H, W, 2)), jnp.float32
        )
        banded = self._banded(fmap1, fmap2, coords, LEVELS)
        ref = corr_lookup_onthefly(fmap1, fmap2, coords, RADIUS, LEVELS)
        np.testing.assert_allclose(
            np.asarray(banded), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_band_boundary_queries(self):
        """Integer and near-integer displacements that park window
        origins exactly on / either side of every band seam (band_rows
        = 3 makes every third row a seam): bitwise vs the resident
        kernel and tolerance vs onthefly."""
        from raft_ncup_tpu.ops.corr import corr_lookup_onthefly

        fmap1, fmap2 = setup()
        for dy in (-1.0, 0.0, 0.5, 1.0):
            coords = coords_grid(B, H, W) + jnp.asarray(
                [0.25, dy], jnp.float32
            )
            banded = self._banded(fmap1, fmap2, coords, LEVELS)
            resident = self._resident(fmap1, fmap2, coords, LEVELS)
            assert np.array_equal(
                np.asarray(banded), np.asarray(resident)
            ), f"dy={dy}"
            if dy == 0.5:  # one cross-path check; bitwise is the pin
                ref = corr_lookup_onthefly(
                    fmap1, fmap2, coords, RADIUS, LEVELS
                )
                np.testing.assert_allclose(
                    np.asarray(banded), np.asarray(ref),
                    rtol=1e-4, atol=1e-4,
                )

    def test_far_oob_windows_bitwise_zero_like_onthefly(self):
        """Displacements larger than the image in all four directions:
        every clamped window lands entirely in a band's zero halo, so
        the output is EXACT zeros — bitwise equal to onthefly (which
        also produces exact zeros), the one case where bitwise
        cross-path parity is mathematically owed."""
        from raft_ncup_tpu.ops.corr import corr_lookup_onthefly

        fmap1, fmap2 = setup()
        big = 4.0 * max(H, W)
        for dx, dy in ((big, 0.0), (-big, 0.0), (0.0, big), (-big, -big)):
            coords = coords_grid(B, H, W) + jnp.asarray(
                [dx, dy], jnp.float32
            )
            banded = self._banded(fmap1, fmap2, coords, LEVELS)
            ref = corr_lookup_onthefly(
                fmap1, fmap2, coords, RADIUS, LEVELS
            )
            assert np.array_equal(np.asarray(banded), np.asarray(ref)), (
                dx, dy,
            )
            assert not np.asarray(banded).any()  # provably the OOB case

    def test_bf16_banded_matches_bf16_resident_bitwise(self):
        """The policy's corr dtype rides the banded tier identically:
        bf16 slab/features with f32 accumulate — still bitwise the
        resident kernel under the same dtype."""
        fmap1, fmap2 = setup()
        coords = coords_grid(B, H, W) + 0.3
        b16 = jnp.bfloat16
        banded = self._banded(
            fmap1.astype(b16), fmap2.astype(b16), coords, LEVELS
        )
        resident = self._resident(
            fmap1.astype(b16), fmap2.astype(b16), coords, LEVELS
        )
        assert np.array_equal(np.asarray(banded), np.asarray(resident))

    def test_query_count_not_multiple_of_block(self):
        """35 queries, query_block 16, band_rows 2: padded tail slots
        ride the last band and must not corrupt real outputs."""
        h, w = 5, 7
        g = np.random.default_rng(13)
        fmap1 = jnp.asarray(g.normal(size=(1, h, w, C)), jnp.float32)
        fmap2 = jnp.asarray(g.normal(size=(1, h, w, C)), jnp.float32)
        coords = coords_grid(1, h, w) + jnp.asarray(
            g.uniform(-2.0, 2.0, (1, h, w, 2)), jnp.float32
        )
        banded = self._banded(fmap1, fmap2, coords, 2, band_rows=2)
        ref = corr_lookup(
            build_corr_pyramid(fmap1, fmap2, 2), coords, RADIUS
        )
        np.testing.assert_allclose(
            np.asarray(banded), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_gradients_still_flow_through_banded_dispatch(self, monkeypatch):
        """The custom-VJP backward (f32 XLA path) is tier-agnostic: with
        every level forced banded, gradients must still match the
        reference — the op stays trainable at banded shapes."""
        from raft_ncup_tpu.ops import corr_pallas as cpk

        fmap1, fmap2 = setup()
        coords = coords_grid(B, H, W) + 0.3
        monkeypatch.setattr(cpk, "fits_vmem", lambda *a, **k: False)
        monkeypatch.setattr(cpk, "band_plan", lambda *a, **k: (3, 4))

        def loss_banded(f1, f2, c):
            return (
                cpk.corr_lookup_pallas(f1, f2, c, RADIUS, LEVELS, True) ** 2
            ).sum()

        def loss_ref(f1, f2, c):
            pyr = build_corr_pyramid(f1, f2, LEVELS)
            return (corr_lookup(pyr, c, RADIUS) ** 2).sum()

        gb = jax.grad(loss_banded, argnums=(0, 1, 2))(fmap1, fmap2, coords)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(fmap1, fmap2, coords)
        for a, b in zip(gb, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
            )


class TestBandPlanAndKnobs:
    """band_plan budget math + the env knobs (the autotuner surface)."""

    def test_band_plan_fits_budget(self):
        from raft_ncup_tpu.ops import corr_pallas as cpk

        # 1080p level-0 shape: residency is out, the plan must fit.
        plan = cpk.band_plan(136, 240, 256, 4)
        assert plan is not None
        band_rows, n_bands = plan
        assert band_rows >= 1 and n_bands >= 1
        assert cpk._banded_vmem_bytes(
            136, 240, 256, 4, band_rows
        ) <= int(0.9 * cpk._VMEM_BYTES)
        # Bands cover every clamped origin row of the padded level.
        hp, _, _ = cpk._padded_hw(136, 240, 4)
        assert band_rows * n_bands >= hp - (2 * 4 + 1)

    def test_band_plan_none_when_nothing_fits(self, monkeypatch):
        from raft_ncup_tpu.ops import corr_pallas as cpk

        monkeypatch.setattr(cpk, "_VMEM_BYTES", 1024)
        assert cpk.band_plan(136, 240, 256, 4) is None

    def test_band_rows_env_override_wins(self, monkeypatch):
        from raft_ncup_tpu.ops import corr_pallas as cpk

        monkeypatch.setenv(cpk.BAND_ROWS_ENV, "5")
        plan = cpk.band_plan(136, 240, 256, 4)
        assert plan is not None and plan[0] == 5
        assert cpk.tuning_meta()["corr_band_rows"] == 5

    def test_query_block_env_override(self, monkeypatch):
        from raft_ncup_tpu.ops import corr_pallas as cpk

        monkeypatch.setenv(cpk.QUERY_BLOCK_ENV, "128")
        assert cpk.effective_query_block() == 128
        assert cpk.tuning_meta()["corr_query_block"] == 128
        monkeypatch.delenv(cpk.QUERY_BLOCK_ENV)
        assert cpk.tuning_meta()["corr_band_rows"] == "auto"

    def test_row_chunk_env_override(self, monkeypatch):
        from raft_ncup_tpu.ops import corr

        assert corr.effective_row_chunk() == 8
        monkeypatch.setenv(corr.ROW_CHUNK_ENV, "16")
        assert corr.effective_row_chunk() == 16
        meta = corr.corr_tuning_meta()
        assert meta["corr_row_chunk"] == 16
        # The overridden chunk still computes the same lookup.
        fmap1, fmap2 = setup()
        coords = coords_grid(B, H, W) + 0.25
        ref = corr.corr_lookup_onthefly(
            fmap1, fmap2, coords, RADIUS, LEVELS, row_chunk=8
        )
        out = corr.corr_lookup_onthefly(
            fmap1, fmap2, coords, RADIUS, LEVELS
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_window_wider_than_a_querys_lanes_falls_back(self):
        """The group body packs a query's K+1 window rows into 16 lanes:
        radius 7 (K+1 = 16) is the widest a kernel tier takes, radius 8
        goes to the XLA path at every level, whatever fits VMEM."""
        from raft_ncup_tpu.ops import corr_pallas as cpk

        f32 = jnp.dtype(jnp.float32)
        assert {t for t, _, _ in cpk._level_tiers(16, 24, 16, 7, 2, f32, 128)} == {
            "kernel"
        }
        assert [t for t, _, _ in cpk._level_tiers(16, 24, 16, 8, 2, f32, 128)] == [
            "fallback", "fallback",
        ]

    def test_dispatch_counts_mutation_is_locked(self):
        """The satellite contract: concurrent traces must not lose
        tally increments (the lock exists; hammer it)."""
        import threading

        from raft_ncup_tpu.ops import corr_pallas as cpk

        cpk.reset_dispatch_counts()
        n_threads, n_iter = 8, 500

        def work():
            for _ in range(n_iter):
                cpk._count("levels_total")

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert cpk.dispatch_counts()["levels_total"] == n_threads * n_iter
        cpk.reset_dispatch_counts()


RADIUS4 = 4  # the published radius: K+1 = 10 columns, two sublane tiles


@functools.lru_cache(maxsize=None)
def _one_level_lookup(tier):
    """One level's kernel, jitted once per tier and dtype (jit's own
    cache): the residue cases differ in their coordinates only."""
    from raft_ncup_tpu.ops import corr_pallas as cpk

    def run(f1, f2l, cflat):
        hw = f2l.shape[1:3]
        if tier == "banded":
            return cpk._banded_lookup_one_level(
                f1, cpk._pad_level(f2l, RADIUS4, 3), hw, cflat, RADIUS4, 0,
                band_rows=3, interpret=True, query_block=16,
            )
        return cpk._lookup_one_level(
            f1, cpk._pad_level(f2l, RADIUS4), hw, cflat, RADIUS4, 0,
            interpret=True, query_block=16,
        )

    return jax.jit(run)


def _residue_cases():
    for name, dtype, tile in (("f32", jnp.float32, 8), ("bf16", jnp.bfloat16, 16)):
        for tier in ("resident", "banded"):
            for res in range(tile):
                yield pytest.param(dtype, tile, tier, res, id=f"{name}-{tier}-r{res}")


class TestColumnResidues:
    """The group body aligns AFTER the channel sum: the window is loaded
    at the tile-aligned column at or before its origin and the shift by
    the residue (origin mod sublane tile: 8 rows of float32, 16 of
    bf16) is applied to the summed correlations. Every residue is its
    own alignment, residue 7 of float32 the only one whose patch reaches
    the window's third column tile, and the right-most clamped origin
    ``wp - (K+1)`` the one whose aligned window ends at the slab's
    allocated width: each against the XLA on-the-fly path, on both
    kernel tiers."""

    H4, W4, C4 = 4, 24, 16  # 1/sqrt(16) is a power of two: exact in bf16

    @pytest.mark.parametrize("dtype,tile,tier,res", list(_residue_cases()))
    def test_every_origin_residue_matches_onthefly(self, dtype, tile, tier, res):
        from raft_ncup_tpu.ops import corr_pallas as cpk
        from raft_ncup_tpu.ops.corr import corr_lookup_onthefly

        h, w, c, r = self.H4, self.W4, self.C4, RADIUS4
        g = np.random.default_rng(100 + res)
        fmap1 = jnp.asarray(g.normal(size=(1, h, w, c)), jnp.float32).astype(dtype)
        fmap2 = jnp.asarray(g.normal(size=(1, h, w, c)), jnp.float32).astype(dtype)
        _, wp, pad = cpk._padded_hw(h, w, r)
        lim = wp - (2 * r + 2)  # the right-most clamped origin
        # Origins res, res + tile, ... up to the clamp, cycled over a
        # row's queries; a row's first query far left (clamped to 0, all
        # taps out of bounds), its last far right (clamped to lim).
        steps = (lim - res) // tile + 1
        origin = res + tile * (np.arange(w) % steps)
        cx = origin + r - pad + g.uniform(0.05, 0.95, (h, w))
        cx[:, 0], cx[:, -1] = -3.0 * w, 3.0 * w
        cy = np.arange(h)[:, None] + g.uniform(-1.5, 1.5, (h, w))
        coords = jnp.asarray(np.stack([cx, cy], -1)[None], jnp.float32)
        ib = np.clip(np.floor(cx).astype(int) - r + pad, 0, lim)
        assert set((ib[:, 1:-1] % tile).ravel()) == {res}
        assert (ib[:, -1] == lim).all() and (ib[:, 0] == 0).all()

        f1 = fmap1.reshape(1, h * w, c) * (1.0 / math.sqrt(c))
        out = _one_level_lookup(tier)(f1, fmap2, coords.reshape(1, h * w, 2))
        ref = corr_lookup_onthefly(
            fmap1.astype(jnp.float32), fmap2.astype(jnp.float32), coords, r, 1
        ).reshape(1, h * w, -1)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )
        assert not np.asarray(out)[0, ::w].any()  # far left: exact zeros
