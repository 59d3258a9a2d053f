#!/usr/bin/env python
"""Execute ONE real 1088x1920 / 32-iteration test-mode forward —
optionally spatially sharded — and report peak RSS + wall time: the
out-of-band evidence behind docs/PERF.md's "1080p executed for real"
and "spatially sharded 1080p executed" rows.

tests/test_highres.py pins the 1080p memory story with *compiler memory
analysis* (platform-independent, cheap); this script is the complement:
it actually executes the flagship onthefly-corr configuration at full
1080p shape and measures what the OS saw. CPU is an honest stand-in for
"does the working set fit": ru_maxrss upper-bounds the XLA temp +
argument + output footprint the analysis predicts (host arenas and the
compiler itself add overhead on top, which is why both numbers are
recorded side by side).

``--spatial N`` (N > 1) runs the SAME forward as one SPMD program on a
(1 data x N spatial) mesh. On a host with fewer than N real devices the
CPU platform is split into N virtual devices
(``--xla_force_host_platform_device_count``, the tests/conftest.py
mechanism), so the report's ``analysis_*`` numbers become PER-DEVICE:
they should drop roughly with the shard count, matching
tests/test_highres.py's compile-time claim — now on an executed
program. Note the CPU-emulation caveat (docs/SHARDING.md): all N
virtual devices share one address space, so ``peak_rss_gib`` still
aggregates every shard; per-device footprint is the ``analysis_*``
fields. ``collectives``/``collective_bytes`` fingerprint the sharding
(0/0 when unsharded).

``--size 2176 3840`` is the UHD/4K configuration the banded Pallas
corr tier (ops/corr_pallas.py; docs/PERF.md "Banded dispatch") exists
for: with ``--corr_impl pallas`` the report's ``corr_dispatch`` field
shows which tier (resident kernel / banded kernel / XLA fallback)
carried each pyramid level, and the executed forward is the evidence
that 4K fits and runs. ``--precision bf16_infer`` runs the same
forward under the bf16 policy — halving the 4K working set — which
was previously unmeasurable out-of-band.

Usage:
    JAX_PLATFORMS=cpu python scripts/highres_forward.py [--iters 32]
        [--size 1088 1920] [--corr_impl onthefly] [--spatial 2]
        [--precision f32]

Prints one JSON line: shape, iters, mesh, precision, compile_s, run_s
(the executed forward, compile excluded), peak_rss_gib, per-device
memory-analysis bytes and collective stats for the same executable,
plus corr_dispatch/corr_tuning when the Pallas tiers are in play.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, nargs=2, default=[1088, 1920],
                   metavar=("H", "W"))
    p.add_argument("--iters", type=int, default=32)
    p.add_argument("--corr_impl", default="onthefly",
                   choices=["onthefly", "volume", "pallas"])
    p.add_argument("--precision", default="f32",
                   choices=["f32", "bf16_infer"],
                   help="precision-policy preset the forward compiles "
                   "under (docs/PRECISION.md); bf16_infer halves the "
                   "corr working set and doubles the Pallas VMEM "
                   "dispatch thresholds")
    p.add_argument("--spatial", type=int, default=1,
                   help="shard the image height over this many devices "
                   "(1 = unsharded). On CPU, forces this many virtual "
                   "host devices BEFORE jax initializes.")
    args = p.parse_args(argv)

    if args.spatial > 1:
        # Must land before the first jax import: device count is fixed
        # at backend init. Harmless when real devices already exist.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.spatial}"
            ).strip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_ncup_tpu.config import flagship_config
    from raft_ncup_tpu.models import get_model
    from raft_ncup_tpu.parallel.mesh import (
        collective_stats,
        make_mesh,
        mesh_fingerprint,
    )
    from raft_ncup_tpu.parallel.step import make_eval_step
    from raft_ncup_tpu.utils.runtime import enable_compilation_cache

    enable_compilation_cache()
    h, w = args.size
    if (h // 8) % args.spatial:
        raise SystemExit(
            f"--spatial {args.spatial} must divide height/8 = {h // 8} "
            "(pad with InputPadder(divisor=8*spatial) first)"
        )
    cfg = flagship_config(
        dataset="sintel", corr_impl=args.corr_impl,
        precision=args.precision,
    )
    model = get_model(cfg)
    variables = model.init(jax.random.PRNGKey(0), (1, 64, 64, 3))

    corr_dispatch = None
    if args.corr_impl == "pallas":
        # Trace-time tier tally (resident kernel / banded / XLA
        # fallback per pyramid level) — read after the single compile
        # below, the one-reset-one-lowering discipline the counts
        # document.
        from raft_ncup_tpu.ops import corr_pallas as cpk

        cpk.reset_dispatch_counts()

    mesh = (
        make_mesh(data=1, spatial=args.spatial,
                  devices=jax.devices()[: args.spatial])
        if args.spatial > 1
        else None
    )
    step = make_eval_step(model, iters=args.iters, mesh=mesh)

    img = jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32)
    t0 = time.perf_counter()
    compiled = step.lower(variables, img, img).compile()
    compile_s = time.perf_counter() - t0
    if args.corr_impl == "pallas":
        corr_dispatch = cpk.dispatch_counts()
    mem = compiled.memory_analysis()
    try:
        coll = collective_stats(compiled.as_text())
    except Exception as e:  # pragma: no cover - backend-specific text
        print(f"collective_stats unavailable: {e}", file=sys.stderr)
        coll = {"collectives": None, "collective_bytes": None}

    rng = np.random.default_rng(0)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)), jnp.float32)
    t0 = time.perf_counter()
    lr, up = compiled(variables, img1, img2)
    jax.block_until_ready((lr, up))
    run_s = time.perf_counter() - t0

    finite = bool(jnp.isfinite(up).all())
    # Linux ru_maxrss is KiB.
    peak_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    from raft_ncup_tpu.ops.corr import corr_tuning_meta

    report = {
        "shape": [1, h, w, 3],
        "iters": args.iters,
        "corr_impl": args.corr_impl,
        "precision": args.precision,
        "platform": jax.default_backend(),
        "mesh": mesh_fingerprint(mesh),
        "devices": args.spatial,
        "compile_s": round(compile_s, 1),
        "run_s": round(run_s, 1),
        "finite": finite,
        "peak_rss_gib": round(peak_rss / 2**30, 2),
        # memory_analysis of an SPMD executable is PER DEVICE: under
        # --spatial N these should drop roughly with N.
        "analysis_temp_gib": round(
            int(mem.temp_size_in_bytes) / 2**30, 2
        ),
        "analysis_total_gib": round(
            (
                int(mem.temp_size_in_bytes)
                + int(mem.argument_size_in_bytes)
                + int(mem.output_size_in_bytes)
            )
            / 2**30,
            2,
        ),
        **coll,
        "corr_tuning": corr_tuning_meta(),
    }
    if corr_dispatch is not None:
        # Which tier carried each pyramid level (three-tier dispatch,
        # ops/corr_pallas.py): the 4K acceptance evidence is
        # fallback == 0 — every level on a kernel tier.
        report["corr_dispatch"] = corr_dispatch
    print(json.dumps(report), flush=True)
    return 0 if finite else 1


if __name__ == "__main__":
    sys.exit(main())
