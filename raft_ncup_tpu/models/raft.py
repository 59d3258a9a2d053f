"""RAFT / RAFT-NCUP model orchestration, TPU-first.

Rather than one monolithic module, the model is a bundle of linen
components (fnet/cnet/update_block/upsampler) plus a pure-JAX forward that
wires them together. This keeps the recurrent refinement a plain
``jax.lax.scan`` — one compiled iteration body regardless of iteration
count — with the GRU hidden state, query coordinates and (when BatchNorm
lives inside the upsampler) mutable batch statistics as the scan carry.
Gradient rematerialization wraps the body during training: of every
iteration the carry survives, and by name (``utils/remat.py``) the lookup's
K*K*L planes at 1/8 resolution and the weights net's two hidden convolution
outputs at 1/4 (0.9 GB a step at the Sintel fine-tune's batch 6, the
dearest work of the second forward per byte held). NCUP's full-resolution
planes are never kept (~2 GB an iteration for ~31 ms a step), so the 12
NCUP passes don't hold live activations.

Reference call structure: core/raft.py:87-143 (baseline) and
core/raft_nc_dbl.py:115-173 (NCUP variant: mask head removed, per-iter
nearest x2 -> NCUP x4 -> values x8).

The refinement is also stated as three stages: ``encode`` produces a
segment carry (GRU state, query coordinates, context features and the
correlation feature maps of one batch), ``refine_segment`` advances it by
any contiguous block of iterations, and ``finalize`` upsamples the final
carry — so N iterations can run as one monolithic scan (``apply``) or as S
scan segments across S jit boundaries. All three share the step body and
the upsampling head of ``apply``; tests/test_model_stages.py holds the two
statements of the forward equal. Only ``finalize`` has a caller outside the
tests (the benchmark's mixed-precision evaluation driver).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# jax.shard_map was promoted out of jax.experimental after 0.4.x; resolve
# whichever this jax ships so the spatially-sharded corr lookup works on
# both (the call sites use the keyword form, identical in both APIs).
if hasattr(jax, "shard_map"):
    _shard_map = jax.shard_map
else:  # pragma: no cover - version-dependent
    from jax.experimental.shard_map import shard_map as _shard_map

from raft_ncup_tpu.config import ModelConfig
from raft_ncup_tpu.nn.extractor import Encoder
from raft_ncup_tpu.nn.update import BasicUpdateBlock, SmallUpdateBlock
from raft_ncup_tpu.nn.upsampler import build_upsampler
from raft_ncup_tpu.ops.corr import (
    build_loop_pyramid,
    corr_lookup,
    corr_lookup_onthefly,
)
from raft_ncup_tpu.ops.geometry import convex_upsample, coords_grid, upflow
from raft_ncup_tpu.ops.geometry import upsample_nearest
# ``jax.named_scope`` that the product-site tally sees too. (The imports
# above keep their line count: a Pallas program's cache key carries the
# line numbers of its call stack, PERF.md section 6, PR 33.)
from raft_ncup_tpu.precision.sites import scope as _scope
from raft_ncup_tpu.utils.remat import LOOKUP_OUT, save_named


def _save_conv_outputs(prim, *_, **__) -> bool:
    """``jax.checkpoint`` policy of the encoders in the training step:
    a convolution's output is saved, everything else is recomputed."""
    return prim is jax.lax.conv_general_dilated_p


class RAFT:
    """Model bundle + functional forward.

    Usage::

        model = RAFT(cfg)
        variables = model.init(rng, (1, 368, 768, 3))
        flows = model.apply(variables, img1, img2, iters=12, train=True)
        flow_lr, flow_up = model.apply(variables, img1, img2, iters=32,
                                       test_mode=True)

    ``variables`` is ``{'params': {...}, 'batch_stats': {...}}``; images are
    NHWC uint8-range float32 in [0, 255] (normalization happens inside, as
    in reference: core/raft.py:90-91).
    """

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        # The precision policy (raft_ncup_tpu/precision/; docs/PRECISION.md)
        # is the single dtype authority: module compute dtype, correlation
        # feature/volume dtype, and the pinned-f32 set (coords, upsampler,
        # outputs, master weights) all come from here.
        self.policy = cfg.precision_policy
        dtype = self.policy.module_dtype
        hdim, cdim = cfg.hidden_dim, cfg.context_dim

        if cfg.small:
            self.fnet = Encoder(128, "instance", cfg.dropout, small=True, dtype=dtype)
            self.cnet = Encoder(
                hdim + cdim, "none", cfg.dropout, small=True, dtype=dtype
            )
            self.update_block = SmallUpdateBlock(
                cfg.corr_planes, hdim, cdim, dtype=dtype
            )
        else:
            self.fnet = Encoder(256, "instance", cfg.dropout, small=False, dtype=dtype)
            self.cnet = Encoder(
                hdim + cdim, "batch", cfg.dropout, small=False, dtype=dtype
            )
            self.update_block = BasicUpdateBlock(
                cfg.corr_planes,
                hdim,
                cdim,
                # raft_nc_dbl deletes the convex mask head (reference:
                # core/raft_nc_dbl.py:68).
                use_mask_head=(cfg.variant == "raft"),
                dtype=dtype,
            )

        self.upsampler = None
        if cfg.variant == "raft_nc_dbl":
            # NCUP consumes 2-channel flow with 128-channel GRU guidance
            # (reference: core/raft_nc_dbl.py:75).
            self.upsampler = build_upsampler(cfg.upsampler, cfg.dataset)

    # ------------------------------------------------------------------ init

    def init(self, rng: jax.Array, image_shape: tuple[int, ...]) -> dict:
        """Initialize all components. ``image_shape`` is NHWC with H, W
        divisible by 8."""
        B, H, W, _ = image_shape
        h8, w8 = H // 8, W // 8
        cfg = self.cfg
        # Template arrays for parameter init ride the policy's master-
        # weight dtype (f32 in every preset).
        pdt = self.policy.param_jnp
        hdim, cdim = cfg.hidden_dim, cfg.context_dim
        kf, kc, ku, kup = jax.random.split(rng, 4)

        img = jnp.zeros((B, H, W, 3), pdt)
        vf = self.fnet.init(kf, img)
        vc = self.cnet.init(kc, img)

        net = jnp.zeros((B, h8, w8, hdim), pdt)
        inp = jnp.zeros((B, h8, w8, cdim), pdt)
        corr = jnp.zeros((B, h8, w8, cfg.corr_planes), pdt)
        flow = jnp.zeros((B, h8, w8, 2), pdt)
        vu = self.update_block.init(ku, net, inp, corr, flow)

        params = {
            "fnet": vf["params"],
            "cnet": vc["params"],
            "update_block": vu["params"],
        }
        batch_stats = {}
        for name, v in (("fnet", vf), ("cnet", vc), ("update_block", vu)):
            if "batch_stats" in v:
                batch_stats[name] = v["batch_stats"]

        if self.upsampler is not None:
            flow2 = jnp.zeros((B, h8 * 2, w8 * 2, 2), pdt)
            guidance = jnp.zeros((B, h8, w8, hdim), pdt)
            vup = self.upsampler.init(kup, flow2, guidance)
            # Parameter-free heads (bilinear) init to an empty group so the
            # apply-side scoping stays uniform across upsampler kinds.
            params["upsampler"] = vup.get("params", {})
            if "batch_stats" in vup:
                batch_stats["upsampler"] = vup["batch_stats"]

        out = {"params": params}
        if batch_stats:
            out["batch_stats"] = batch_stats
        return out

    # ------------------------------------------------- shared forward pieces

    def _make_run(self, params, bstats, bn_train, rngs):
        """The submodule-application closure shared by every forward
        entry point; mutates ``bstats`` in place when ``bn_train``."""

        def run(name, module, *args, remat_policy=None, **kwargs):
            # Only the upsampler may be parameter-free (bilinear head): its
            # empty group gets dropped by flatten/unflatten round-trips
            # (checkpoint merge). For every other submodule absence is a
            # truncated checkpoint and must keep failing loudly.
            if name == "upsampler":
                v = {"params": params.get(name, {})}
            else:
                v = {"params": params[name]}
            if name in bstats:
                v["batch_stats"] = bstats[name]
            mutable = ["batch_stats"] if bn_train and name in bstats else False

            def apply(v, *args):
                return module.apply(
                    v, *args, mutable=mutable, rngs=rngs, **kwargs
                )

            if remat_policy is not None:
                # Nothing of this submodule outlives its forward: the
                # backward runs it again (outer checkpoint) and, while it
                # differentiates that second run, keeps what the policy
                # names and recomputes the rest (inner checkpoint).
                apply = jax.checkpoint(
                    jax.checkpoint(apply, policy=remat_policy)
                )
            out = apply(v, *args)
            if mutable:
                out, mut = out
                bstats[name] = mut["batch_stats"]
            return out

        return run

    def _encode(
        self, run, image1, image2, *, train=False, bn_train=False,
        flow_init=None, net_init=None, net_warm=None, remat=False,
    ):
        """Everything before the first refinement iteration: normalize,
        siamese fnet, context cnet, warm-start select, initial query
        coordinates. Returns ``(fmap1, fmap2, net, inp, coords1)``.

        ``remat`` (the training step): each encoder is rematerialised.
        Its forward leaves nothing behind; its backward, which comes after
        the whole refinement loop's, runs it again and keeps of that
        second run the convolutions' outputs alone, recomputing what lies
        between them (bias, norm, relu, residual add). Kept whole, the
        encoders' activations at 1/2 and 1/4 resolution outlived the
        entire loop, forward and backward: 1.6 of the 1.9 GiB a sample
        that the Sintel step held at its peak (PERF.md section 6, PR 26).
        The loop's own checkpoint keeps more, by name (``apply``;
        ``utils/remat.py``): what it keeps is stacked over the iterations
        and read back inside the loop, under the peak the step already
        has, where an encoder's outputs would be live across all of it."""
        cfg = self.cfg
        policy = self.policy
        if image1.shape[1] % 8 or image1.shape[2] % 8:
            raise ValueError(
                f"image H, W must be divisible by 8, got {image1.shape[1:3]}; "
                "pad inputs with raft_ncup_tpu.ops.InputPadder first"
            )
        hdim = cfg.hidden_dim

        img1 = 2.0 * (image1 / 255.0) - 1.0
        img2 = 2.0 * (image2 / 255.0) - 1.0

        # Siamese feature extraction: both frames through fnet in one batch
        # (reference: core/extractor.py:168-174). jax.named_scope labels
        # carry into the HLO metadata, by which a capture's reduction
        # gives device seconds per stage (docs/OBSERVABILITY.md).
        policy_kw = {"remat_policy": _save_conv_outputs} if remat else {}
        with _scope("raft.fnet"):
            fmaps = run(
                "fnet",
                self.fnet,
                jnp.concatenate([img1, img2], axis=0),
                train=train,
                bn_train=bn_train,
                **policy_kw,
            )
        fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)
        # Correlation features/volume ride the policy's corr dtype — the
        # dominant memory term, so the bf16 presets halve it (and double
        # the Pallas VMEM dispatch thresholds). Coordinates stay at the
        # policy's pinned f32; the lookups promote through them.
        fmap1 = fmap1.astype(policy.corr_jnp)
        fmap2 = fmap2.astype(policy.corr_jnp)

        with _scope("raft.cnet"):
            cnet_out = run(
                "cnet", self.cnet, img1, train=train, bn_train=bn_train,
                **policy_kw,
            )
        net = jnp.tanh(cnet_out[..., :hdim])
        inp = jax.nn.relu(cnet_out[..., hdim:])
        if net_init is not None:
            # Carried GRU state replaces the cold init per batch row; the
            # select (not arithmetic blend) keeps cold rows bitwise equal
            # to a run without any carry. `inp` is deliberately NOT
            # carried: it is the context encoding of the CURRENT frame —
            # an input feature, not recurrent state — and reusing a stale
            # frame's encoding would feed the update GRU wrong data.
            carried = net_init.astype(net.dtype)
            if net_warm is None:
                net = carried
            else:
                net = jnp.where(
                    net_warm[:, None, None, None], carried, net
                )

        B, H, W, _ = image1.shape
        coords1 = coords_grid(B, H // 8, W // 8)
        if flow_init is not None:
            coords1 = coords1 + flow_init
        return fmap1, fmap2, net, inp, coords1

    def _build_corr_fn(
        self, fmap1, fmap2, mesh=None, spatial_axis="spatial", *, train=False
    ):
        """Correlation-lookup closure over a micro-batch's feature maps,
        per ``cfg.corr_impl`` (volume / onthefly / pallas). ``train``: the
        program differentiates the lookup, which the ``volume`` path's choice
        of a pyramid and a contraction needs to know (``ops/corr.py``)."""
        cfg = self.cfg
        policy = self.policy
        radius = cfg.resolved_corr_radius
        if cfg.corr_impl == "volume":
            pyramid = build_loop_pyramid(
                fmap1, fmap2, cfg.corr_levels, dtype=policy.corr_jnp,
                differentiated=train,
            )

            def corr_fn(coords):
                return corr_lookup(pyramid, coords, radius)

        elif cfg.corr_impl == "onthefly":
            n_spatial = (
                mesh.shape.get(spatial_axis, 1) if mesh is not None else 1
            )
            n_data = mesh.shape.get("data", 1) if mesh is not None else 1
            shardable = (
                n_spatial > 1
                and "data" in (mesh.shape if mesh is not None else {})
                and fmap1.shape[1] % n_spatial == 0
                and fmap1.shape[0] % n_data == 0
            )
            if shardable:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                def corr_fn(coords):
                    f2r = jax.lax.with_sharding_constraint(
                        fmap2, NamedSharding(mesh, P())
                    )

                    def local(f1_loc, f2_full, c_loc):
                        return corr_lookup_onthefly(
                            f1_loc, f2_full, c_loc, radius, cfg.corr_levels,
                            dtype=policy.corr_jnp,
                        )

                    return _shard_map(
                        local,
                        mesh=mesh,
                        in_specs=(
                            P("data", spatial_axis),
                            P(),
                            P("data", spatial_axis),
                        ),
                        out_specs=P("data", spatial_axis),
                    )(fmap1, f2r, coords)

            else:

                def corr_fn(coords):
                    return corr_lookup_onthefly(
                        fmap1, fmap2, coords, radius, cfg.corr_levels,
                        dtype=policy.corr_jnp,
                    )

        elif cfg.corr_impl == "pallas":
            from raft_ncup_tpu.ops.corr_pallas import (
                corr_lookup_pallas,
                prepare_lookup,
            )

            # Dispatch is per pyramid level inside the op, THREE tiers:
            # levels whose padded slab fits the VMEM budget take the
            # resident kernel, levels past residency with a fitting
            # band_plan take the BANDED kernel (at 1080p f32: levels
            # 0-1 banded, 2-3 resident; at 4K every level lands on a
            # kernel tier), and only the remainder takes the XLA
            # on-the-fly path. Shapes are static at trace time, so this
            # is a compile-time choice.
            # Mosaic compiles for the TPU only; elsewhere the kernel
            # runs in interpret mode (slow but correct) so
            # corr_impl='pallas' works everywhere.
            from raft_ncup_tpu.utils.runtime import is_tpu_backend

            interpret = not is_tpu_backend()
            # The pooled, zero-padded pyramid depends on the features
            # alone: made here, once per pair, and closed over by every
            # iteration's lookup (the compiler leaves the pads inside
            # the loop otherwise). A cache of fmap2, not a second input:
            # gradients take the op's own backward through the features.
            prepared = jax.lax.stop_gradient(
                prepare_lookup(
                    fmap1, fmap2, radius, cfg.corr_levels, policy.corr_jnp
                )
            )

            def corr_fn(coords):
                return corr_lookup_pallas(
                    fmap1, fmap2, coords, radius, cfg.corr_levels, interpret,
                    policy.corr_jnp, prepared,
                )

        else:
            raise ValueError(f"unknown corr_impl: {cfg.corr_impl!r}")
        return corr_fn

    def _upsample(self, run, flow_lr, net, bn_train=False):
        """Low-res flow -> full-res prediction, per variant. The convex
        mask is a function of ``net`` alone and is computed here, so it
        runs wherever a prediction is upsampled: once after the loop in
        test mode, in every iteration of the training forward."""
        cfg = self.cfg
        policy = self.policy
        if cfg.variant == "raft_nc_dbl":
            # nearest x2, NCUP x4, values x8 (reference:
            # core/raft_nc_dbl.py:107-112,161). The upsampler runs at
            # the policy's pinned f32 — outside the reference's
            # autocast region, and NCUP's confidence arithmetic is
            # ratio-of-sums (docs/PRECISION.md).
            flow2 = upsample_nearest(flow_lr, 2)
            guidance = net.astype(policy.upsampler_jnp)
            # The upsampler's only train-dependent piece is BatchNorm in
            # the weights-estimation net, so it takes the bn flag.
            hr = run(
                "upsampler", self.upsampler, flow2, guidance, train=bn_train
            )
            return 8.0 * hr
        with _scope("raft.mask_head"):
            up_mask = run("update_block", self.update_block, net, method="mask")
        if up_mask is None:
            return upflow(flow_lr, 8, align_corners=cfg.align_corners)
        return convex_upsample(
            flow_lr, up_mask.astype(policy.upsampler_jnp), 8
        )

    def _gru_context(self, run, inp):
        """What the update block makes of the context features, once per
        pair: ``inp`` is the same in every iteration, so the refinement
        loop closes over this and never reads ``inp`` itself."""
        with _scope("raft.gru_context"):
            return run("update_block", self.update_block, inp, method="context")

    def _make_step(
        self, run, corr_fn, coords0, gru_ctx, bstats, *, test_mode,
        bn_train, early_exit_tol=None,
    ):
        """One refinement iteration on the ``(net, coords1, stats)``
        carry — the single step body every scan (monolithic or segment)
        runs, so segmented execution can never drift from ``apply``.
        ``gru_ctx``: :meth:`_gru_context`'s, constants of the loop.
        In test mode the body is lookup, GRU and flow head and nothing
        else: no prediction is upsampled inside the loop, so the convex
        mask head does not run there and no mask rides the carry.

        ``early_exit_tol`` (test mode only; docs/PERF.md "Early exit"):
        per-sample convergence detection on the GRU's own flow delta.
        The carry's ``stats['converged']`` (B,) bool marks lanes whose
        mean |delta| fell below the tolerance on an EARLIER iteration;
        those lanes' ``(net, coords1)`` are frozen via
        ``jnp.where`` — a select, so a lane converged at iteration k is
        BITWISE the state it had after k (the same select contract as
        the streaming warm start). The mask is sticky and the freeze
        reads the mask from step ENTRY, so the iteration that detects
        convergence still commits its own update. Everything stays on
        device: no shape change, no host pull, no recompile.
        """
        policy = self.policy
        if early_exit_tol is not None and not test_mode:
            raise ValueError("early_exit_tol requires test_mode=True")

        def step(carry, _):
            net, coords1, stats = carry
            # Restore mutable stats captured in the carry so `run` sees the
            # per-iteration BatchNorm state (upsampler only).
            if "upsampler" in stats:
                bstats["upsampler"] = stats["upsampler"]
            net_in, coords1_in = net, coords1
            coords1 = jax.lax.stop_gradient(coords1)  # .detach() per iter
            # Stage labels inside the scanned refinement iteration: the
            # lookup and the GRU update are the two halves an xprof
            # trace needs separated (correlation memory wall vs compute).
            with _scope("raft.corr_lookup"):
                corr = checkpoint_name(corr_fn(coords1), LOOKUP_OUT)
            flow = coords1 - coords0
            with _scope("raft.update_block"):
                net, delta = run(
                    "update_block",
                    self.update_block,
                    net,
                    gru_ctx,
                    corr,
                    flow.astype(net.dtype),
                    method="step",
                )
            # The coordinate carry is the refinement's f32 backbone: the
            # (possibly bf16) delta joins it at the policy's pinned
            # coord dtype, so per-iteration compute error never narrows
            # the carried state (the error-budget argument).
            coords1 = coords1 + delta.astype(policy.coord_jnp)

            converged = None
            if early_exit_tol is not None:
                frozen = stats["converged"]  # mask at step ENTRY
                keep = frozen[:, None, None, None]
                net = jnp.where(keep, net_in, net)
                coords1 = jnp.where(keep, coords1_in, coords1)
                # Detection norm: mean |delta| per sample, in the pinned
                # coord dtype and in LOW-RES pixels (the 8x upsampling
                # scales displacements, so tol=t low-res px bounds the
                # remaining full-res drift by ~8t px per skipped iter).
                dnorm = jnp.mean(
                    jnp.abs(delta.astype(policy.coord_jnp)), axis=(1, 2, 3)
                )
                converged = frozen | (dnorm < early_exit_tol)

            if test_mode:
                out = None
            else:
                with _scope("raft.upsample"):
                    out = self._upsample(
                        run, coords1 - coords0, net, bn_train
                    )
            new_stats = dict(stats)
            if "upsampler" in stats:
                new_stats["upsampler"] = bstats["upsampler"]
            if converged is not None:
                new_stats["converged"] = converged
                if "exec_iters" in stats:
                    # Per-lane executed-iteration count: a lane active at
                    # step entry pays this iteration; a frozen lane does
                    # not. (refine_segment counts at segment granularity
                    # instead.)
                    new_stats["exec_iters"] = stats["exec_iters"] + (
                        ~frozen
                    ).astype(jnp.int32)
            return (net, coords1, new_stats), out

        return step

    # ----------------------------------------------------------------- apply

    def apply(
        self,
        variables: dict,
        image1: jax.Array,
        image2: jax.Array,
        iters: int = 12,
        flow_init: Optional[jax.Array] = None,
        test_mode: bool = False,
        train: bool = False,
        freeze_bn: bool = False,
        rngs: Optional[dict] = None,
        remat: bool = True,
        mutable: bool = False,
        mesh=None,
        spatial_axis: str = "spatial",
        metric_head: Optional[Any] = None,
        net_init: Optional[jax.Array] = None,
        net_warm: Optional[jax.Array] = None,
        return_net: bool = False,
        early_exit_tol: Optional[float] = None,
        return_exec_iters: bool = False,
    ):
        """Estimate optical flow between a pair of NHWC image batches.

        Returns (train mode) the stacked per-iteration high-res flow
        predictions (iters, B, H, W, 2); (test_mode) the tuple
        ``(flow_lowres, flow_up)``. With ``mutable=True`` additionally
        returns the updated batch_stats as a second element. In test mode
        the prediction is upsampled once, after the loop, from the state
        the loop leaves (convex mask head or NCUP): with ``iters=0`` that
        is the initial ``net`` and the initial flow.

        ``metric_head`` (test mode only): a traceable callable applied to
        the final high-res flow INSIDE this program; the second result
        element becomes ``metric_head(flow_up)`` instead of the full
        field. Evaluation folds its on-device metric accumulators
        (inference/metrics.py) through this hook so the compiled eval
        program emits a handful of scalars per batch — the full flow
        field never leaves the device on the validation path.

        ``net_init``/``net_warm``/``return_net`` (streaming warm start,
        raft_ncup_tpu/streaming/): ``net_init`` is a (B, H/8, W/8,
        hidden_dim) GRU hidden state carried from a previous frame;
        rows where the (B,)-bool ``net_warm`` is True START the
        refinement from it instead of the context encoder's
        ``tanh`` initialization (a ``jnp.where`` select, so cold rows
        are BITWISE the default cold start — the streaming engine's
        per-stream isolation contract). ``return_net=True`` (test mode
        only) appends the final hidden state to the result:
        ``(flow_lr, flow_up, net)``.

        ``mesh``/``spatial_axis``: when running under a (data x spatial)
        SPMD mesh, the on-the-fly correlation lookup is wrapped in
        ``jax.shard_map`` over the spatial axis — queries stay row-sharded
        while fmap2 is replicated (33 MB at 1/8 res of 1080p). Left to the
        GSPMD partitioner, the lookup's scan-over-row-chunks structure
        partitions pathologically (6x the single-device temp memory,
        measured in tests/test_highres.py); the explicit map makes spatial
        sharding actually reduce per-device memory.

        ``early_exit_tol``/``return_exec_iters`` (test mode only;
        docs/PERF.md "Early exit"): with a tolerance set, the refinement
        runs as a ``lax.while_loop`` whose condition is ``t < iters AND
        any lane still active`` — per-sample convergence freezes a
        lane's carry bitwise (see ``_make_step``), and the batch-level
        condition genuinely stops the loop once every lane converged,
        which is what makes the FLOP cut real rather than
        compute-and-discard. The condition never leaves the device and
        the carry shapes are identical to the scan's, so the cache key,
        sharding and donation story are unchanged.
        ``return_exec_iters=True`` appends the per-sample (B,) int32
        executed-iteration count as the LAST result element.
        """
        if early_exit_tol is not None and not test_mode:
            raise ValueError("early_exit_tol requires test_mode=True")
        if return_exec_iters and early_exit_tol is None:
            raise ValueError(
                "return_exec_iters requires early_exit_tol (without "
                "detection every lane runs the full budget by definition)"
            )

        policy = self.policy
        params = variables["params"]
        bstats = dict(variables.get("batch_stats", {}))
        bn_train = train and not freeze_bn

        run = self._make_run(params, bstats, bn_train, rngs)
        fmap1, fmap2, net, inp, coords1 = self._encode(
            run, image1, image2, train=train, bn_train=bn_train,
            flow_init=flow_init, net_init=net_init, net_warm=net_warm,
            remat=train and remat,
        )
        corr_fn = self._build_corr_fn(
            fmap1, fmap2, mesh, spatial_axis, train=train
        )

        B, H, W, _ = image1.shape
        coords0 = coords_grid(B, H // 8, W // 8)

        step = self._make_step(
            run, corr_fn, coords0, self._gru_context(run, inp), bstats,
            test_mode=test_mode, bn_train=bn_train, early_exit_tol=early_exit_tol,
        )

        init_stats: dict = {}
        if bn_train and "upsampler" in bstats:
            init_stats["upsampler"] = bstats["upsampler"]
        if early_exit_tol is not None:
            init_stats["converged"] = jnp.zeros((B,), jnp.bool_)
            init_stats["exec_iters"] = jnp.zeros((B,), jnp.int32)

        body = step
        if train and remat:
            # recomputed in the backward, but for what the policy keeps
            body = jax.checkpoint(step, policy=save_named)

        with _scope("raft.refinement"):
            if early_exit_tol is not None:
                # while_loop, not scan: the loop condition — all on
                # device — exits the moment every lane converged, so
                # trailing iterations cost nothing at all (test mode has
                # no per-iteration outputs, so no stacked outs to keep).
                def _cond(state):
                    t, (_n, _c, stats) = state
                    return jnp.logical_and(
                        t < iters, jnp.any(~stats["converged"])
                    )

                def _body(state):
                    t, carry = state
                    carry, _ = body(carry, None)
                    return t + jnp.int32(1), carry

                _, (net, coords1, final_stats) = jax.lax.while_loop(
                    _cond, _body,
                    (jnp.int32(0), (net, coords1, init_stats)),
                )
                flow_seq = None
            else:
                (net, coords1, final_stats), flow_seq = jax.lax.scan(
                    body, (net, coords1, init_stats), None, length=iters
                )
        if "upsampler" in final_stats:
            bstats["upsampler"] = final_stats["upsampler"]

        if test_mode:
            with _scope("raft.upsample"):
                flow_up = self._upsample(
                    run, coords1 - coords0, net, bn_train
                ).astype(policy.output_jnp)  # serving/metrics contract: f32
            if metric_head is not None:
                with _scope("raft.metric_head"):
                    flow_up = metric_head(flow_up)
            if return_net:
                result = (coords1 - coords0, flow_up, net)
            else:
                result = (coords1 - coords0, flow_up)
            if return_exec_iters:
                result = result + (final_stats["exec_iters"],)
        else:
            if metric_head is not None:
                raise ValueError("metric_head requires test_mode=True")
            if return_net:
                raise ValueError("return_net requires test_mode=True")
            result = flow_seq

        if mutable:
            return result, bstats
        return result

    # ------------------------------------------- composable scan segments

    def encode(
        self,
        variables: dict,
        image1: jax.Array,
        image2: jax.Array,
        flow_init: Optional[jax.Array] = None,
        net_init: Optional[jax.Array] = None,
        net_warm: Optional[jax.Array] = None,
        rngs: Optional[dict] = None,
        early_exit: bool = False,
    ) -> dict:
        """Front half (inference): everything before the first
        refinement iteration, returned as a SEGMENT CARRY dict —

        - ``net`` / ``coords1``: the live recurrent state a refinement
          iteration mutates (all of it: the convex mask is computed by
          ``finalize`` from the last ``net``);
        - ``inp`` / ``fmap1`` / ``fmap2``: the batch's immutable context,
          which travels with the state from segment to segment (the next
          segment rebuilds its correlation closure from these).

        ``early_exit=True`` seeds the convergence-detection keys the
        early-exit segments read and update: ``converged`` (B,) bool
        (all False — every lane starts active) and ``exec_iters`` (B,)
        int32 (zeros). They ride the carry between segments like the
        rest of the state; ``finalize`` ignores them.

        ``encode -> refine_segment x S -> finalize`` reproduces
        ``apply(test_mode=True)`` exactly: same submodule code, same
        step body, same upsampling head.
        """
        run = self._make_run(
            variables["params"], dict(variables.get("batch_stats", {})),
            False, rngs,
        )
        fmap1, fmap2, net, inp, coords1 = self._encode(
            run, image1, image2,
            flow_init=flow_init, net_init=net_init, net_warm=net_warm,
        )
        carry = {
            "net": net, "coords1": coords1, "inp": inp,
            "fmap1": fmap1, "fmap2": fmap2,
        }
        if early_exit:
            B = net.shape[0]
            carry["converged"] = jnp.zeros((B,), jnp.bool_)
            carry["exec_iters"] = jnp.zeros((B,), jnp.int32)
        return carry

    def refine_segment(
        self,
        variables: dict,
        carry: dict,
        iters: int,
        mesh=None,
        spatial_axis: str = "spatial",
        rngs: Optional[dict] = None,
        early_exit_tol: Optional[float] = None,
    ) -> dict:
        """Advance a segment carry by ``iters`` contiguous refinement
        iterations (one ``lax.scan`` — one compiled iteration body, as
        in ``apply``) and return the updated carry. The correlation
        closure is rebuilt from the carry's own feature maps, so a
        carry handed in across a jit boundary refines identically to
        one that never left its program; for the
        'volume' impl this re-derives the pyramid per segment — one
        matmul + avg-pools, cheap against a segment of GRU iterations,
        and bitwise the same pyramid every time.

        ``early_exit_tol`` (carry must be seeded with
        ``encode(..., early_exit=True)``): per-iteration convergence
        detection and freeze run INSIDE the segment — flow is identical
        to the monolithic early-exit path — but the executed-iters
        count quantizes to SEGMENT boundaries: a lane active at segment
        entry is billed the whole segment, because a segment seam is
        the first point a lane's exit is observable from outside the
        scan. So ``exec_iters(segmented) == ceil(exec_iters(monolithic)
        / seg_len) * seg_len``.
        """
        run = self._make_run(
            variables["params"], dict(variables.get("batch_stats", {})),
            False, rngs,
        )
        corr_fn = self._build_corr_fn(
            carry["fmap1"], carry["fmap2"], mesh, spatial_axis
        )
        B, h8, w8 = carry["net"].shape[:3]
        coords0 = coords_grid(B, h8, w8)
        stats = {}
        if early_exit_tol is not None:
            if "converged" not in carry:
                raise ValueError(
                    "early_exit_tol requires a carry seeded with "
                    "encode(..., early_exit=True)"
                )
            stats["converged"] = carry["converged"]
        step = self._make_step(
            run, corr_fn, coords0, self._gru_context(run, carry["inp"]), {},
            test_mode=True, bn_train=False, early_exit_tol=early_exit_tol,
        )
        with _scope("raft.refinement"):
            (net, coords1, out_stats), _ = jax.lax.scan(
                step, (carry["net"], carry["coords1"], stats),
                None, length=iters,
            )
        out = dict(carry)
        out["net"] = net
        out["coords1"] = coords1
        if early_exit_tol is not None:
            out["converged"] = out_stats["converged"]
            # Segment-granularity billing (see docstring): lanes active
            # at segment ENTRY pay the full segment.
            entry_active = ~carry["converged"]
            out["exec_iters"] = carry["exec_iters"] + iters * (
                entry_active.astype(jnp.int32)
            )
        return out

    def finalize(
        self,
        variables: dict,
        carry: dict,
        rngs: Optional[dict] = None,
        return_net: bool = False,
    ):
        """Back half: upsample a finished segment carry to the
        test-mode result ``(flow_lr, flow_up)`` (plus ``net`` with
        ``return_net`` — the streaming warm-start handoff). The convex
        mask head runs here, on the carry's ``net``, as after ``apply``'s
        loop."""
        run = self._make_run(
            variables["params"], dict(variables.get("batch_stats", {})),
            False, rngs,
        )
        B, h8, w8 = carry["net"].shape[:3]
        coords0 = coords_grid(B, h8, w8)
        flow_lr = carry["coords1"] - coords0
        with _scope("raft.upsample"):
            flow_up = self._upsample(run, flow_lr, carry["net"]).astype(
                self.policy.output_jnp
            )
        if return_net:
            return flow_lr, flow_up, carry["net"]
        return flow_lr, flow_up


@functools.lru_cache(maxsize=8)
def get_model(cfg: ModelConfig) -> RAFT:
    """Model registry/factory keyed by (hashable, frozen) config."""
    return RAFT(cfg)
