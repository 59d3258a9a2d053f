#!/usr/bin/env python
"""Demo driver: run flow on a folder of frames and write visualizations.

The reference pops cv2 windows (reference: demo.py:44-47); headless TPU
hosts have no display, so visualizations are written to ``--output``
(png side-by-side of frame and colorized flow) instead, with ``--show``
restoring the interactive behavior.

Example:
    python demo.py --model checkpoints/raft_chairs --path demo-frames
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None) -> None:
    from raft_ncup_tpu.cli import add_model_args, model_config_from_args
    from raft_ncup_tpu.io import read_image
    from raft_ncup_tpu.models.raft import RAFT
    from raft_ncup_tpu.ops import InputPadder
    from raft_ncup_tpu.viz import flow_to_image

    parser = argparse.ArgumentParser(description="RAFT flow demo (TPU)")
    parser.add_argument("--path", required=True, help="folder of frames")
    parser.add_argument("--output", default="demo_out")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--show", action="store_true")
    parser.add_argument("--restore_ckpt", default=None,
                        help="alias of --model for our CLI symmetry")
    add_model_args(parser)
    from raft_ncup_tpu.cli import add_platform_arg, apply_platform

    add_platform_arg(parser)
    args = parser.parse_args(argv)
    apply_platform(args)
    from raft_ncup_tpu.utils.runtime import enable_compilation_cache

    enable_compilation_cache()

    # In the reference demo, --model is the checkpoint path (demo.py:52-53)
    # and the architecture is plain raft. Keep that: if --model points at a
    # file/dir treat it as the checkpoint.
    ckpt = args.restore_ckpt
    if os.path.exists(args.model):
        ckpt, args.model = args.model, "raft"

    model_cfg = model_config_from_args(args, dataset="sintel")
    model = RAFT(model_cfg)

    from evaluate import load_variables

    variables = load_variables(model, model_cfg, ckpt)

    files = sorted(
        glob.glob(os.path.join(args.path, "*.png"))
        + glob.glob(os.path.join(args.path, "*.jpg"))
    )
    if len(files) < 2:
        raise SystemExit(f"need >= 2 frames in {args.path}")
    os.makedirs(args.output, exist_ok=True)

    @jax.jit
    def forward(variables, img1, img2):
        return model.apply(
            variables, img1, img2, iters=args.iters, test_mode=True
        )

    for f1, f2 in zip(files[:-1], files[1:]):
        img1 = read_image(f1).astype(np.float32)[None]
        img2 = read_image(f2).astype(np.float32)[None]
        padder = InputPadder(img1.shape)
        p1, p2 = padder.pad(jnp.asarray(img1), jnp.asarray(img2))
        _, flow_up = forward(variables, p1, p2)
        # unpad on device (pure slice), then ONE explicit pull per frame —
        # np.asarray here would be an implicit d2h sync (JGL001's runtime
        # analogue).
        flow = jax.device_get(padder.unpad(flow_up)[0])

        vis = np.concatenate(
            [img1[0].astype(np.uint8), flow_to_image(flow)], axis=0
        )
        out = os.path.join(
            args.output, os.path.splitext(os.path.basename(f1))[0] + "_flow.png"
        )
        import cv2

        cv2.imwrite(out, vis[:, :, ::-1])
        print(f"{f1} -> {out}")
        if args.show:
            cv2.imshow("flow", vis[:, :, ::-1] / 255.0)
            cv2.waitKey()


if __name__ == "__main__":
    main(sys.argv[1:])
