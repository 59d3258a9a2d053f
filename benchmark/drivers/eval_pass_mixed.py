"""Driver ``eval_pass_mixed``: ``eval_pass`` for a configuration whose stated
precision is a POLICY (``model.precision: "bf16_infer"``: bfloat16 products
with float32 accumulation in the encoders and the update block, float32 pins
around them) and not "float32 everywhere". Set-up, the window, its counts,
the gap to the reference and ``close`` are ``eval_pass``'s own functions; what
differs is what ``correct`` holds the timed forward to. The deciding reference
is ``benchmark/reference/raft.py`` (float32 at ``highest``), always;
``raft_infer_mixed.py`` (the policy as explicit roundings) is the base of the
controls, of the lookup's and NCUP's site rows, and information.

- **The window's counts and the flow's gap**, ``eval_pass``'s three rows:
  ``window_px_count_gap``, ``window_nonfinite_sums`` (a bfloat16 accumulator
  stalls and miscounts: these fail) and ``flow_gap_mean_px``, ``check_pairs``
  pairs through the window's own executable and pass with the FLOAT32
  reference's flow as ground truth.
- **The compiler's module**, read and not asked: the timed executable's jitted
  function lowered once more on the shapes of its first call
  (``ShapeCachedForward.lowered_hlo``), every instruction by scope
  (``benchmark/hlo_products.py``; the counting is ``train_steps_mixed``'s):
  ``hlo_compute_products_not_bf16``, ``hlo_pinned_ops_narrow`` (the pinned
  scopes here are the lookup, NCUP and the metric head),
  ``hlo_sums_not_f32``. All 0. One row more than the training cell's, for
  the half of a pin that no element type shows:
  ``hlo_pinned_products_not_highest``, the convolutions and dots under the
  pinned scopes (NCUP's weights net: the lookup's sums are multiplies and
  reductions) whose ``operand_precision`` is anything but ``highest`` for
  both operands, 0; none found there counts as one. The float32 program at
  the TPU's default precision reads 3.
- **The program's own tally** of the timed executable's product sites
  (``ShapeCachedForward.report()["precision"]``, banked when the executable
  was built), held to the configuration's ``precision`` block:
  ``pinned_sites_not_f32``, ``compute_sites_not_bf16``, both 0, and
  ``f32_product_sites_gap`` (the float32 sites against ``pinned_sites``, name
  for name), 0: the number the metric ``infer_f32_product_sites`` reports. A
  program without that report (a parent of PR 39) is refused at once, before
  anything compiles.
- **One site at a time**, on seeded operands at the cell's 1/8 grid:
  ``product_site_gap`` (``train_steps_mixed``'s: the program's ``conv2d`` /
  ``SplitConv2d`` / ``build_corr_pyramid`` against the policy's product, the
  row that holds the ACCUMULATION, with the control in the program's place in
  every run, ``accumulate_bf16_site_gap_negated``); ``lookup_site_gap`` (the
  program's ``build_corr_pyramid`` + ``corr_lookup`` against the reference's
  bfloat16 pyramid and float32 lookup on the same features and coordinates,
  relative L2: what holds P7's weights and window sums, which have no product
  instruction); ``upsample_site_gap_px`` (the program's NCUP,
  ``model.finalize``, on the MIXED REFERENCE's low-resolution flow and hidden
  state of one sampled pair against the reference's NCUP on the same state,
  mean px: what holds P9, which a forward's gap cannot tell from the
  iterations' noise).

The controls (``readings.py --control``) are the mixed reference in the
program's place with one statement of the policy dropped, each named in the
configuration's ``control.drop``: its site rows first (cheap), then its whole
forward on the check's pairs against the float32 reference
(``<control>.<row>``). ``readings.py`` without a switch prints the program's
rows and ``mixed.flow_gap_mean_px`` (the program against the mixed reference
on one pair: what two roundings of one policy differ by). ``readings.py
--model-precision f32`` reads the float32 program at the TPU's default matmul
precision in the program's place: its flow's gap reads inside the program's
band, and the tally, the module and the site rows are what refuse it.
"""

from __future__ import annotations

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import eval_pass as base
from benchmark.drivers.train_steps_mixed import (
    HLO, TALLY, _counts, _hlo_report, _program_sites, _site_gaps,
)
from benchmark.harness import NoResult, compared, emit
from benchmark.reference.raft import reference_flow
from benchmark.reference.raft_infer_mixed import (
    CONTROLS, MixedInferReference, lookup_site, lookup_site_inputs, mixed_reference_flow,
)
from benchmark.reference.raft_train_mixed import site_inputs, site_products

close = base.close


def _refuse_without_report() -> None:
    from raft_ncup_tpu.inference.pipeline import ShapeCachedForward

    if not hasattr(ShapeCachedForward, "report") or not hasattr(ShapeCachedForward, "lowered_hlo"):
        raise NoResult(
            "this program's ShapeCachedForward reports no product-site tally of its "
            "executable (report / lowered_hlo): the cell's compared rows cannot be read"
        )


def setup(cell) -> dict:
    _refuse_without_report()
    return base.setup(cell)


# ------------------------------------------------------ the program's tally


def _precision_report(cell, fwd) -> dict:
    """The timed executable's product sites as the pass reports them, and the
    three counts ``correct`` compares (``train_steps_mixed._precision_report``
    for a forward: operands AND result, the float32 sites name for name)."""
    stated = cell.config["precision"]
    report = fwd.report()["precision"]
    if not report:
        raise NoResult("the pass reports no product sites of its executable")
    sites = report["sites"]
    compute = tuple(stated["compute_scopes"])
    in_compute = {path: path.split("/")[0] in compute for path in sites}
    f32 = sorted(path for path, s in sites.items() if s["operands"] == "float32")
    return {
        **report,
        "pinned_sites_not_f32": sum(
            1 for path, s in sites.items()
            if not in_compute[path] and (s["operands"], s["result"]) != ("float32", "float32")
        ),
        "compute_sites_not_bf16": sum(
            1 for path, s in sites.items()
            if in_compute[path]
            and (s["operands"] != "bfloat16" or s["result"] not in ("bfloat16", "float32"))
        ),
        "f32_product_sites_gap": len(set(f32) ^ set(stated["pinned_sites"])),
    }


def run(state, seconds: float) -> dict:
    window = base.run(state, seconds)
    window["report"] = {"precision": _precision_report(state["cell"], state["fwd"])}
    return window


# ------------------------------------------------------- one site at a time


def _grid(cell) -> tuple:
    h, w = cell.traffic["native_hw"]
    return -(-h // 8), -(-w // 8)


def _rel_gap(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel()))


def _program_lookup(model_cfg, f1, f2, coords):
    """The program's P6 + P7 at one site: its own pyramid at the policy's
    storage dtype and its own lookup, as the forward runs them: TWO programs,
    the levels handed from one to the other as the arrays they are stored in
    (in the forward they are operands of the loop the lookup runs in). In one
    program the compiler may drop a level's cast down and up again between
    the pooling and the lookup (``xla_allow_excess_precision``), and on a v5e
    it did: the lookup read levels 1-3 BEFORE their rounding, 8.1e-4 from the
    statement and nothing the forward does (PERF.md section 6, PR 39)."""
    from raft_ncup_tpu.ops.corr import CorrPyramid, build_corr_pyramid, corr_lookup

    policy, radius = model_cfg.precision_policy, model_cfg.resolved_corr_radius
    pyramid = jax.jit(
        lambda f1, f2: build_corr_pyramid(f1, f2, model_cfg.corr_levels, dtype=policy.corr_jnp).levels
    )(f1, f2)
    hw = tuple(f1.shape[1:3])
    return jax.jit(lambda levels, c: corr_lookup(CorrPyramid(levels, hw), c, radius))(pyramid, coords)


def _site_rows(cell, state) -> list:
    """``product_site_gap`` with its control beside it, and
    ``lookup_site_gap``."""
    model_cfg, params = state["fwd"].model.cfg, state["variables"]["params"]
    inputs = site_inputs(params, cell.seed, _grid(cell))
    want = site_products(params, inputs)
    program = _site_gaps(_program_sites(model_cfg, params, inputs), want)
    control = _site_gaps(site_products(params, inputs, drop="accumulate_bf16"), want)
    f1, f2, coords = lookup_site_inputs(cell.seed, _grid(cell), int(cell.config["widths"]["fnet_dim"]))
    levels, radius = model_cfg.corr_levels, model_cfg.resolved_corr_radius
    lookup = _rel_gap(
        _program_lookup(model_cfg, f1, f2, coords), lookup_site(f1, f2, coords, levels, radius)
    )
    emit({"phase": "sites", "program": program, "accumulate_bf16": control,
          "lookup_site_gap": lookup})
    limit = cell.limit("product_site_gap")
    return [
        compared("product_site_gap", max(program.values()), limit),
        compared("accumulate_bf16_site_gap_negated", -min(control.values()), -limit),
        compared("lookup_site_gap", lookup, cell.limit("lookup_site_gap")),
    ]


def _mean_epe(a, b) -> float:
    return float(jnp.sqrt(((jnp.asarray(a) - jnp.asarray(b)) ** 2).sum(-1)).mean())


def _upsample_row(cell, state) -> dict:
    """``upsample_site_gap_px``, and as information the program's flow
    against the mixed reference's on the same pair (one batch more through
    the pass, the mixed reference's flow as ground truth)."""
    t, fwd = cell.traffic, state["fwd"]
    pair = state["pool"][base._sample(cell, state["pool"])[0]]
    mixed = MixedInferReference(cell.config["model"])
    flow, (net, coords1) = mixed_reference_flow(
        mixed, state["variables"], pair["image1"], pair["image2"], int(t["iters"])
    )
    policy = fwd.model.cfg.precision_policy
    carry = {"net": net.astype(policy.compute_jnp), "coords1": coords1}
    _, got = jax.jit(fwd.model.finalize)(state["variables"], carry)
    gap = _mean_epe(got, mixed.upsample(state["variables"], net, coords1))
    acc = base._pass(state, base.PoolDataset([{**pair, "flow": flow}], int(t["batch_size"])))
    emit({"phase": "mixed_reference", "upsample_site_gap_px": gap,
          "mixed.flow_gap_mean_px": float(acc[0] / acc[1])})
    return compared("upsample_site_gap_px", gap, cell.limit("upsample_site_gap_px"))


_PRODUCT = re.compile(r"=\s*\w+\[[^\]]*\](?:\{[^}]*\})?\s+(?:convolution|dot)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HIGHEST = "operand_precision={highest,highest}"


def _pinned_products_not_highest(text: str, pinned) -> int:
    """The convolutions and dots of an HLO module's text whose ``op_name``
    stands under one of the scopes ``pinned`` and which do not say
    ``highest`` for both operands (jax's default prints no
    ``operand_precision`` at all; ``hlo_products.products`` lists element
    types and not this attribute). No product under a pinned scope is itself
    a reading off the statement, 1."""
    held = []
    for line in text.splitlines():
        if _PRODUCT.search(line):
            op_name = _OP_NAME.search(line)
            if op_name and any(scope in op_name.group(1) for scope in pinned):
                held.append(line)
    return sum(1 for line in held if _HIGHEST not in line) + (not held)


def _policy_rows(cell, state, precision: dict) -> list:
    """Every row of ``correct`` but ``eval_pass``'s three: the module's
    counts, the tally's, the sites'."""
    text = state["fwd"].lowered_hlo()
    if text is None:
        raise NoResult("the pass's executable was never called: nothing to lower")
    hlo = _hlo_report(cell, text)
    name = "hlo_pinned_products_not_highest"
    hlo[name] = _pinned_products_not_highest(text, cell.config["precision"]["pinned_scopes"])
    emit({"phase": "hlo", **hlo})
    return [
        *_counts(cell, hlo, HLO + (name,)),
        *_counts(cell, precision, TALLY),
        *_site_rows(cell, state),
        _upsample_row(cell, state),
    ]


def check(state, window: dict) -> list:
    return [
        *base.check(state, window),
        *_policy_rows(state["cell"], state, window["report"]["precision"]),
    ]


def reading(cell, seconds: float) -> list:
    """The program's reading for ``readings.py``: the check's rows without a
    window. With ``--model-precision f32`` the float32 program at jax's
    default matmul precision stands in the program's place."""
    _refuse_without_report()
    f32_program = cell.config["model"]["precision"] == "f32"
    precision = (
        jax.default_matmul_precision("default") if f32_program else contextlib.nullcontext()
    )
    state = base._build(cell)
    with precision:
        rows = [base._gap_to_reference(state)]  # builds and runs the pass's executable
        return rows + _policy_rows(cell, state, _precision_report(cell, state["fwd"]))


def control(cell) -> list:
    """Every control of the configuration in the program's place: its rows at
    the sites first (every control's, before any forward), then its whole
    forward on the pairs ``check`` samples against the float32 reference."""
    drops = list(cell.config["control"]["drop"])
    for drop in drops:
        if drop not in CONTROLS:
            raise NoResult(f"the mixed reference has no control {drop!r}: {CONTROLS}")
    t = cell.traffic
    state = base._build(cell)
    variables, params, iters = state["variables"], state["variables"]["params"], int(t["iters"])
    levels, radius = state["ref"].levels, state["ref"].radius
    inputs = site_inputs(params, cell.seed, _grid(cell))
    want = site_products(params, inputs)
    f1, f2, coords = lookup_site_inputs(cell.seed, _grid(cell), int(cell.config["widths"]["fnet_dim"]))
    want_lookup = lookup_site(f1, f2, coords, levels, radius)
    picks = base._sample(cell, state["pool"])
    pairs = [state["pool"][i] for i in picks]
    sound = MixedInferReference(cell.config["model"])
    _, (net, coords1) = mixed_reference_flow(
        sound, variables, pairs[0]["image1"], pairs[0]["image2"], iters
    )
    want_up = sound.upsample(variables, net, coords1)
    lows = {drop: MixedInferReference(cell.config["model"], drop=drop) for drop in drops}
    rows = []
    for drop, low in lows.items():
        gaps = _site_gaps(site_products(params, inputs, drop=drop), want)
        rows += [
            compared(f"{drop}.product_site_gap", min(gaps.values()), cell.limit("product_site_gap")),
            compared(f"{drop}.lookup_site_gap",
                     _rel_gap(lookup_site(f1, f2, coords, levels, radius, drop), want_lookup),
                     cell.limit("lookup_site_gap")),
            compared(f"{drop}.upsample_site_gap_px",
                     _mean_epe(low.upsample(variables, net, coords1), want_up),
                     cell.limit("upsample_site_gap_px")),
        ]
        emit({"phase": "control_sites", "seed": cell.seed, "drop": drop,
              **{r["check"]: r["value"] for r in rows[-3:]}})
    want_flows = [
        reference_flow(state["ref"], variables, p["image1"], p["image2"], iters) for p in pairs
    ]
    for drop, low in lows.items():
        gaps = [
            _mean_epe(mixed_reference_flow(low, variables, p["image1"], p["image2"], iters)[0], want)
            for p, want in zip(pairs, want_flows)
        ]
        emit({"phase": "control", "seed": cell.seed, "drop": drop, "gaps": gaps})
        rows.append(compared(f"{drop}.flow_gap_mean_px", float(np.mean(gaps)),
                             cell.limit("flow_gap_mean_px")))
    return rows
