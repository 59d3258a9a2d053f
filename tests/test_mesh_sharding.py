"""Mesh-first inference/serving/streaming (docs/SHARDING.md).

Sharding regressions must fail fast, not only under ``-m slow``
(tests/test_highres.py keeps the 1080p-scale claims): these tests run
the REAL subsystems on the forced 8-virtual-device CPU platform
(tests/conftest.py) at small shapes and pin

- ``make_mesh`` device-coverage honesty (a stripped device is a loud
  warning, never silence),
- the mesh fingerprint in every ``ShapeCachedForward`` cache key
  (sharded and unsharded executables can never collide),
- sharded-vs-unsharded numerical parity for the forward, the serving
  data path, the streaming warm-start step, and an eval validator pass,
- the guard-clean steady state (zero implicit host transfers, zero
  steady-state recompiles) under the mesh.
"""

from __future__ import annotations

import warnings

import jax
import numpy as np
import pytest

from raft_ncup_tpu.config import (
    ServeConfig,
    StreamConfig,
    small_model_config,
)
from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
from raft_ncup_tpu.models import get_model
from raft_ncup_tpu.parallel.mesh import make_mesh, mesh_fingerprint

HW = (32, 32)  # h8=4: divides spatial=2, tiny compiles


@pytest.fixture(scope="module")
def small_model():
    cfg = small_model_config("raft", dataset="chairs")
    model = get_model(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, *HW, 3))
    return model, variables


def _mesh(data=1, spatial=2):
    return make_mesh(
        data=data, spatial=spatial, devices=jax.devices()[: data * spatial]
    )


def _img(seed, hw=HW, batch=1):
    g = np.random.default_rng(seed)
    return (g.random((batch, *hw, 3)) * 255.0).astype(np.float32)


# ------------------------------------------------------------- make_mesh


class TestMakeMesh:
    def test_warns_loudly_when_devices_stripped(self):
        """Satellite regression: data*spatial < n used to silently strip
        the extra devices — a mis-sized mesh that idles 6 of 8 chips
        must announce itself."""
        with pytest.warns(UserWarning, match="only 2 of 8"):
            mesh = make_mesh(data=1, spatial=2)
        assert dict(mesh.shape) == {"data": 1, "spatial": 2}

    def test_exact_coverage_is_silent(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            mesh = make_mesh(data=4, spatial=2)  # exactly the 8 devices
            make_mesh(data=1, spatial=2, devices=jax.devices()[:2])
        assert dict(mesh.shape) == {"data": 4, "spatial": 2}

    def test_oversubscription_still_raises(self):
        with pytest.raises(ValueError, match="needs 16 devices"):
            make_mesh(data=8, spatial=2)

    def test_fingerprint_identity(self):
        assert mesh_fingerprint(None) == "nomesh"
        fp = mesh_fingerprint(_mesh(1, 2))
        assert fp == "mesh(data=1,spatial=2:cpu)"
        assert fp != mesh_fingerprint(_mesh(2, 1))

    def test_make_mesh_takes_no_third_axis(self):
        """There is no `pipe` axis: the keyword is gone, not ignored (a
        mesh that accepted it used to idle the devices it named)."""
        with pytest.raises(TypeError, match="pipe"):
            make_mesh(data=1, spatial=1, pipe=4, devices=jax.devices()[:4])

    def test_two_axis_mesh_is_what_it_was(self):
        """Axis names, shape and fingerprint of the one mesh there is:
        every compiled-program key and provenance string minted against
        it stays valid. data=None spans what spatial leaves."""
        mesh = _mesh(1, 2)
        assert tuple(mesh.axis_names) == ("data", "spatial")
        assert mesh.devices.shape == (1, 2)
        assert mesh_fingerprint(mesh) == "mesh(data=1,spatial=2:cpu)"
        auto = make_mesh(spatial=2)
        assert dict(auto.shape) == {"data": 4, "spatial": 2}
        with pytest.raises(ValueError, match="not divisible by spatial=3"):
            make_mesh(spatial=3)

    def test_resolve_config_mesh_refuses_a_triple(self):
        from raft_ncup_tpu.parallel.mesh import resolve_config_mesh

        with pytest.raises(ValueError):
            resolve_config_mesh(None, (1, 1, 4))
        mesh2, div2 = resolve_config_mesh(None, (1, 2))
        assert dict(mesh2.shape) == {"data": 1, "spatial": 2}
        assert div2 == 16
        assert resolve_config_mesh(None, None) == (None, 8)


# ------------------------------------------------------ collective_stats


class TestCollectiveStats:
    """Per-op-kind breakout (``by_op``) next to the aggregate counters
    the highres/uhd bench rows already consume — pipeline handoffs
    (collective-permute) must be attributable separately from halo
    exchanges and fmap2 all-gathers."""

    def test_by_op_breakout_and_aggregates(self):
        from raft_ncup_tpu.parallel.mesh import collective_stats

        hlo = (
            "  %cp = f32[2,4]{1,0} collective-permute(%x), channel_id=1\n"
            "  %cp2 = f32[2,4]{1,0} collective-permute-start(%y)\n"
            "  %cp3 = f32[2,4]{1,0} collective-permute-done(%cp2)\n"
            "  %ag = bf16[8]{0} all-gather(%z), dimensions={0}\n"
            "  not_an_op collective-permute(%q)\n"
        )
        cs = collective_stats(hlo)
        cp = cs["by_op"]["collective-permute"]
        # The -done half of the async pair (and the no-result line)
        # must not double count.
        assert cp == {"count": 2, "bytes": 2 * (2 * 4 * 4)}
        assert cs["by_op"]["all-gather"] == {"count": 1, "bytes": 16}
        assert cs["collectives"] == 3
        assert cs["collective_bytes"] == 64 + 16

    def test_unsharded_program_is_all_zeros(self):
        """Existing consumers (bench ``highres_collectives`` /
        ``highres_collective_bytes``, scripts/highres_forward.py) index
        the named aggregate keys; every op kind is present zero-filled
        so by_op consumers never need existence guards."""
        from raft_ncup_tpu.parallel.mesh import (
            _COLLECTIVE_OPS,
            collective_stats,
        )

        cs = collective_stats("%r = f32[4]{0} add(%a, %b)\n")
        assert cs["collectives"] == 0 and cs["collective_bytes"] == 0
        assert set(cs["by_op"]) == set(_COLLECTIVE_OPS)
        assert all(
            v == {"count": 0, "bytes": 0} for v in cs["by_op"].values()
        )


# -------------------------------------------------- cache-key isolation


class _DummyModel:
    """apply()-compatible stand-in: cache-key tests need no compile."""

    def apply(self, variables, image1, image2, **kw):
        return image1, image2


class TestMeshKeyedCache:
    def test_every_cache_key_carries_the_mesh_fingerprint(self):
        mesh = _mesh(1, 2)
        sharded = ShapeCachedForward(_DummyModel(), {}, mesh=mesh)
        plain = ShapeCachedForward(_DummyModel(), {})

        def build():
            return lambda *a: a

        sharded.custom(("stream", 2), build)
        plain.custom(("stream", 2), build)
        (skey,) = sharded._fns
        (pkey,) = plain._fns
        assert skey[0] == mesh_fingerprint(mesh)
        assert pkey[0] == "nomesh"
        assert skey != pkey  # same logical key, different executables

    def test_config_rejects_batch_not_divisible_by_data_axis(self):
        with pytest.raises(ValueError, match="not divisible by mesh"):
            ServeConfig(batch_sizes=(1, 2), mesh=(2, 1))
        with pytest.raises(ValueError, match="not divisible by mesh"):
            StreamConfig(batch_sizes=(1, 2, 4), mesh=(4, 2))
        # data=1 spatial-only meshes impose nothing on batch sizes.
        assert ServeConfig(mesh=(1, 2)).mesh == (1, 2)

    def test_config_rejects_pad_bucket_off_the_mesh_divisor(self):
        """Mesh pads round to 8*spatial, and InputPadder rejects a
        bucket the divisor doesn't divide — that must be a config-time
        error, not an exception escaping FlowServer.submit() past the
        terminal-status contract."""
        with pytest.raises(ValueError, match="pad divisor 8\\*spatial"):
            ServeConfig(mesh=(1, 3), pad_bucket=64)
        with pytest.raises(ValueError, match="pad divisor 8\\*spatial"):
            StreamConfig(mesh=(1, 3), pad_bucket=64)
        # A bucket the divisor divides is fine.
        assert ServeConfig(mesh=(1, 2), pad_bucket=32).pad_bucket == 32

    def test_stream_config_refuses_a_mesh_triple(self):
        """The engine's config holds the same rule as the server's
        (tests/test_model_stages.py): a mesh is (data, spatial)."""
        with pytest.raises(ValueError, match="two positive sizes"):
            StreamConfig(mesh=(1, 1, 2))
        assert StreamConfig(mesh=(1, 2)).mesh == (1, 2)

    def test_cli_mesh_spec(self):
        import argparse

        from raft_ncup_tpu.cli import str2mesh

        assert str2mesh("1,2") == (1, 2)
        for bad in ("2", "0,2", "1,1,2", "1,1,2,2"):
            with pytest.raises(argparse.ArgumentTypeError):
                str2mesh(bad)

    def test_cli_mesh_triple_is_a_usage_error(self, capsys):
        """`--mesh 1,1,2` stops at parse time with a message that says a
        mesh is two sizes; the pair still builds the two-axis mesh."""
        import argparse

        from raft_ncup_tpu.cli import add_mesh_arg, mesh_from_args

        parser = argparse.ArgumentParser()
        add_mesh_arg(parser)
        with pytest.raises(SystemExit) as e:
            parser.parse_args(["--mesh", "1,1,2"])
        assert e.value.code == 2
        assert "DATA,SPATIAL, two positive sizes" in capsys.readouterr().err
        args = parser.parse_args(["--mesh", "1,2"])
        with pytest.warns(UserWarning, match="only 2 of 8"):
            mesh = mesh_from_args(args)
        assert mesh.axis_names == ("data", "spatial")
        assert mesh_from_args(parser.parse_args([])) is None


# ------------------------------------------------------ forward parity


class TestShardedParity:
    def test_forward_sharded_matches_unsharded(self, small_model):
        model, variables = small_model
        plain = ShapeCachedForward(model, variables)
        sharded = ShapeCachedForward(model, variables, mesh=_mesh(1, 2))
        i1, i2 = _img(1), _img(2)
        lr_p, up_p = plain(i1, i2, iters=2)
        lr_s, up_s = sharded(i1, i2, iters=2)
        np.testing.assert_allclose(lr_s, lr_p, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(up_s, up_p, rtol=1e-4, atol=1e-4)

    def test_eval_validator_sharded_parity(self, small_model):
        """The tier-1 eval parity check (promoted out of the slow tier):
        a (2 data x 2 spatial) mesh validator pass over the held-out
        synthetic split must reproduce the unsharded EPE — this is the
        whole-pipeline parity (EvalPipeline staging shardings + on-device
        metric fold + SPMD forward), small enough to fail fast on every
        run."""
        from raft_ncup_tpu.evaluation import validate_synthetic

        model, variables = small_model
        kw = dict(
            iters=2, batch_size=2, size_hw=(64, 64), length=4, seed=999
        )
        ref = validate_synthetic(model, variables, None, **kw)
        out = validate_synthetic(
            model, variables, None, mesh=_mesh(2, 2), **kw
        )
        assert ref and out
        np.testing.assert_allclose(
            out["synthetic"], ref["synthetic"], rtol=1e-4
        )

    def test_serve_sharded_parity(self, small_model):
        """One request through a spatially-sharded FlowServer must return
        the same flow as the unsharded server (pads ride 8*spatial, the
        compiled program is SPMD, the drain pull is unchanged)."""
        from raft_ncup_tpu.serving import FlowServer

        model, variables = small_model
        cfg = ServeConfig(batch_sizes=(1,), iter_levels=(2,))
        img1, img2 = _img(3)[0], _img(4)[0]
        flows = {}
        for tag, mesh in (("plain", None), ("sharded", _mesh(1, 2))):
            with FlowServer(model, variables, cfg, mesh=mesh) as server:
                res = server.submit(img1, img2).result(timeout=120.0)
                assert res.ok, res.detail
                flows[tag] = res.flow
                assert server.report()["mesh"] == mesh_fingerprint(mesh)
        assert flows["plain"].shape == flows["sharded"].shape == (*HW, 2)
        np.testing.assert_allclose(
            flows["sharded"], flows["plain"], rtol=1e-4, atol=1e-4
        )

    def test_stream_sharded_parity_and_guard_clean(self, small_model):
        """Two warm-chained frames through a spatially-sharded
        StreamEngine (mesh from StreamConfig.mesh — the serve.py --mesh
        path) must match the unsharded engine bitwise-or-tolerance on
        BOTH frames (the second one exercises the sharded slot-table
        gather → in-graph splat → scatter chain), and the sharded steady
        state must stay guard-clean: zero implicit host transfers, zero
        recompiles after warmup."""
        from raft_ncup_tpu.analysis.guards import (
            GuardStats,
            RecompileWatchdog,
            forbid_host_transfers,
        )
        from raft_ncup_tpu.streaming import StreamEngine

        model, variables = small_model
        frames = [(_img(5)[0], _img(6)[0]), (_img(6)[0], _img(7)[0])]
        results = {}
        for tag, mesh_spec in (("plain", None), ("sharded", (1, 2))):
            cfg = StreamConfig(
                capacity=1, frame_hw=HW, iters=2, batch_sizes=(1,),
                queue_capacity=8, mesh=mesh_spec,
            )
            eng = StreamEngine(model, variables, cfg)
            try:
                eng.warmup()
                out = []
                stats = GuardStats()
                with RecompileWatchdog() as wd, forbid_host_transfers(
                    stats
                ):
                    for i1, i2 in frames:
                        r = eng.submit("s", i1, i2).result(timeout=120.0)
                        assert r.ok, r.detail
                        out.append(r.flow)
                results[tag] = out
                assert wd.count == 0, f"{tag}: recompiled under traffic"
                assert stats.host_transfers == 0, tag
                assert eng.report()["mesh"] == (
                    "mesh(data=1,spatial=2:cpu)"
                    if mesh_spec
                    else "nomesh"
                )
            finally:
                eng.drain()
        for k in range(2):
            np.testing.assert_allclose(
                results["sharded"][k], results["plain"][k],
                rtol=1e-4, atol=1e-4,
                err_msg=f"frame {k} (k=1 is the warm-started one)",
            )
