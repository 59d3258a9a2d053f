#!/usr/bin/env python
"""Evaluation driver (reference-compatible CLI).

Validates on chairs / sintel / kitti or writes leaderboard submissions
(reference: evaluate.py:185-272). Checkpoints: an orbax run dir produced
by our train.py, or a PyTorch ``.pth`` from the reference (imported
weight-by-weight).

Examples:
    python evaluate.py --model raft_nc_dbl --dataset sintel \
        --restore_ckpt checkpoints/raft_nc_sintel
    python evaluate.py --model raft_nc_dbl --dataset kitti --submission \
        --restore_ckpt models/raft_nc-kitti.pth
"""

from __future__ import annotations

import sys

import jax


def load_variables(model, model_cfg, restore_ckpt: str | None):
    """Init variables, then overwrite from the checkpoint (strict for
    torch files, as in the reference eval — evaluate.py:257)."""
    import os

    # Parameter shapes are input-size independent (fully convolutional);
    # init small to keep startup cheap.
    shape = (1, 64, 96, 3)
    variables = model.init(jax.random.PRNGKey(0), shape)
    if not restore_ckpt:
        return variables
    if os.path.isdir(restore_ckpt):
        from raft_ncup_tpu.training.checkpoint import restore_variables

        restored = restore_variables(restore_ckpt)
        variables["params"] = restored["params"]
        if "batch_stats" in restored:
            variables["batch_stats"] = restored["batch_stats"]
        return variables
    from raft_ncup_tpu.training.checkpoint import load_torch

    return load_torch(restore_ckpt, variables, strict=True)


def main(argv=None) -> None:
    from raft_ncup_tpu.cli import parse_eval
    from raft_ncup_tpu.models.raft import RAFT

    args, model_cfg, data_cfg = parse_eval(argv)
    from raft_ncup_tpu.utils.runtime import enable_compilation_cache

    enable_compilation_cache()
    model = RAFT(model_cfg)
    variables = load_variables(model, model_cfg, args.restore_ckpt)

    if args.export_pth:
        # Serialize the loaded checkpoint as a reference-keyed .pth the
        # reference's strict DataParallel eval load consumes directly
        # (reference: evaluate.py:246-257).
        from raft_ncup_tpu.utils.torch_export import save_torch_checkpoint

        save_torch_checkpoint(args.export_pth, variables)
        print(f"exported reference-keyed checkpoint to {args.export_pth}")
        return

    # --mesh DATA,SPATIAL is the first-class surface (docs/SHARDING.md);
    # --spatial_parallel N stays as reference-era shorthand for 1,N.
    from raft_ncup_tpu.cli import mesh_from_args

    mesh = mesh_from_args(args)
    if mesh is None and args.spatial_parallel > 1:
        from raft_ncup_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(data=1, spatial=args.spatial_parallel)

    from raft_ncup_tpu.utils.profiling import trace

    with trace(args.trace_dir):
        _evaluate(args, model, variables, data_cfg, mesh)
    # evaluate.py has no warm-up of its own: each dataset's shapes build
    # their executables as the first pass meets them, so the start-up
    # line is printed once everything has been built.
    from raft_ncup_tpu.observability import startup_line

    print(startup_line(), file=sys.stderr)


def _evaluate(args, model, variables, data_cfg, mesh) -> None:
    from raft_ncup_tpu.evaluation import (
        VALIDATORS,
        create_kitti_submission,
        create_sintel_submission,
    )

    iters_kw = {"iters": args.iters} if args.iters is not None else {}
    val_kw = dict(iters_kw)
    if getattr(args, "batch_size", None):
        val_kw["batch_size"] = args.batch_size
    if args.submission:
        if args.dataset == "sintel":
            kwargs = dict(iters_kw)
            if args.output_path:
                kwargs["output_path"] = args.output_path
            create_sintel_submission(
                model, variables, data_cfg,
                warm_start=args.warm_start, write_png=args.write_png,
                mesh=mesh, **kwargs,
            )
        elif args.dataset == "kitti":
            kwargs = dict(iters_kw)
            if args.output_path:
                kwargs["output_path"] = args.output_path
            create_kitti_submission(
                model, variables, data_cfg, write_png=args.write_png,
                mesh=mesh, **kwargs,
            )
        else:
            raise SystemExit("--submission supports sintel/kitti only")
        return

    results = VALIDATORS[args.dataset](
        model, variables, data_cfg, mesh=mesh, **val_kw
    )
    print(results)


if __name__ == "__main__":
    main(sys.argv[1:])
