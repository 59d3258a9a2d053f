"""Driver ``train_steps_mixed``: ``train_steps`` for a configuration whose
stated precision is a POLICY (``model.precision: "bf16_train"``: bfloat16
products with float32 accumulation in the encoders and the update block,
float32 pins around them) and not "float32 everywhere". Set-up, the window,
its counter rows and ``close`` are ``train_steps``'s own functions; what
differs is what ``correct`` holds the timed step to. The deciding reference is
``benchmark/reference/raft_train.py`` (float32 at ``highest``), always;
``raft_train_mixed.py`` (the policy as explicit roundings) is the base of the
controls, the policy's product at one site, and information.

- **The step's gaps** to the float32 reference, from three calls of the TIMED
  executable on the window's first batch: ``train_steps``'s four
  (``loss_rel_gap``, ``grad_rel_gap``, ``grad_rel_gap_worst_module``,
  ``loss_after_steps_rel_gap``) and ``grad_rel_gap_upsampler`` (NCUP's own
  parameters' clipped gradient: what a dropped upsampler pin moves). At this
  size two optimizer steps move the loss by 4 to 20%, so the loss after them
  sees an update that was not applied (PERF.md section 2).
- **The compiler's module**, read and not asked: the timed step function
  lowered once more on the run's own state and the check's batch, every
  instruction of its HLO by scope (``benchmark/hlo_products.py``). ``hlo_compute_products_not_bf16``:
  convolutions and dots under the configuration's ``compute_scopes``, and the
  volume's, with an operand that is not bfloat16; ``hlo_pinned_ops_narrow``:
  instructions under ``pinned_scopes`` that compute on a type narrower than
  float32; ``hlo_sums_not_f32``: forward products of the sites the policy
  says hand out their float32 accumulator (``float32_sums``: the GRU gates'
  two parts, the flow head's thin-output product, the volume) whose result is
  anything else. All 0.
- **The program's own tally** of the timed step's product sites
  (``raft_ncup_tpu/precision/sites.py``, banked with the executable), held to
  the configuration's ``precision`` block: ``pinned_sites_not_f32``,
  ``compute_sites_not_bf16`` (operands bfloat16, the sum handed out in
  bfloat16 or float32), both 0, and ``f32_product_sites_gap``, the count of
  float32 sites less the ``pinned_sites`` the configuration lists, 0: the
  number the metric ``train_f32_product_sites`` reports. A program without
  the tally (a parent of PR 37) is refused at once, before anything compiles.
- **One product at a time** (``product_site_gap``): one site of every form of
  product the policy names (``raft_train_mixed.SITES``), the seed's kernel on
  seeded inputs, through the program's own ``conv2d`` / ``SplitConv2d`` /
  ``build_corr_pyramid`` at the policy's dtypes against the policy's product
  written out; the largest relative gap over the sites. This is the row that
  holds the ACCUMULATION (a whole step cannot: two sound bfloat16 steps stand
  further apart than a step with rounded partial sums stands from either),
  and every run shows it can: ``accumulate_bf16_site_gap_negated`` is the
  control's SMALLEST site gap, negated, against the negated limit.

The controls (``readings.py --control``) are the mixed reference in the
program's place with one statement of the policy dropped, each named in the
configuration's ``control.drop``: first every control's site row
(``<control>.product_site_gap``, cheap), then its step's gaps
(``<control>.<gap>``, a whole reference step each). ``readings.py`` without a
switch prints the program's rows and its gaps to the mixed reference as
``mixed.<gap>`` (what two roundings of one policy differ by; the float32 rows
say what bfloat16 costs the loss). ``readings.py --model-precision f32`` reads
the float32 program at the TPU's default matmul precision in the program's
place: the deciding rows alone.

Adding a mixed-precision training cell, as files and entries only: what
``train_steps`` lists, with ``traffic/<traffic>.json`` naming this driver,
the configuration's ``model.precision`` a bfloat16 preset, its ``precision``
block (``compute_scopes``, ``pinned_scopes``, ``float32_sums``,
``pinned_sites``, the cast points) and ``control.drop``, and in
``limits/<workload>.json`` the rows above.
"""

from __future__ import annotations

import contextlib
import importlib

import jax
import jax.numpy as jnp

from benchmark import hlo_products
from benchmark.drivers import train_steps as base
from benchmark.harness import NoResult, compared, emit
from benchmark.reference.raft_train_mixed import (
    CONTROLS, SITES, MixedTrainReference, site_inputs, site_params, site_products,
)

close = base.close
VOLUME = "bxc,byc->bxy"  # the all-pairs product's name in the module (its einsum)


def _refuse_without_tally() -> None:
    try:
        importlib.import_module("raft_ncup_tpu.precision.sites")
    except ImportError:
        raise NoResult(
            "this program has no product-site tally (raft_ncup_tpu/precision/"
            "sites.py): the cell's compared rows cannot be read"
        )


def setup(cell) -> dict:
    _refuse_without_tally()
    return base.setup(cell)


# ------------------------------------------------------ the program's tally


def _precision_report(cell) -> dict:
    """The timed step's product sites, as the program banked them when it
    built the executable, and the three counts ``correct`` compares."""
    from raft_ncup_tpu.inference.costs import get_cost_ledger

    policy, stated = cell.config["model"]["precision"], cell.config["precision"]
    entry = get_cost_ledger().lookup(kind="train_step", policy=policy) or {}
    sites = entry.get("product_sites") or {}
    if not sites:
        raise NoResult(f"the cost ledger holds no product sites of a {policy} train step")
    compute = tuple(stated["compute_scopes"])
    in_compute = {path: path.split("/")[0] in compute for path in sites}
    f32 = sorted(path for path, s in sites.items() if s["operands"] == "float32")
    return {
        "policy": policy,
        "sites_bf16": sum(1 for s in sites.values() if s["operands"] == "bfloat16"),
        "sites_f32": len(f32),
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
        "sites": sites,
    }


def run(state, seconds: float) -> dict:
    window = base.run(state, seconds)
    window["report"]["precision"] = _precision_report(state["cell"])
    return window


# ------------------------------------------------------ the compiler's module


def _hlo_report(cell, text: str) -> dict:
    """The three counts of the lowered step's HLO (the module's docstring
    says what each counts) and the element types found, by scope."""
    stated = cell.config["precision"]
    compute = [s for s in stated["compute_scopes"] if s.startswith("raft.")]
    pinned = list(stated["pinned_scopes"])
    found = hlo_products.products(text, compute + pinned)
    forward = [p for p in found if "transpose(" not in p["op_name"]]
    held = [p for p in found if p["scope"] in compute]
    held += [p for p in forward if p["scope"] is None and VOLUME in p["op_name"]]
    not_bf16 = [p for p in held if set(p["operands"]) != {"bf16"}]
    sums = {pattern: [p for p in forward if pattern in p["op_name"]] for pattern in stated["float32_sums"]}
    narrow = hlo_products.narrow_ops(text, pinned)
    by_scope: dict = {}
    for p in found:
        key = f"{p['scope']}: {'x'.join(p['operands'])}->{p['result']}"
        by_scope[key] = by_scope.get(key, 0) + 1
    return {
        "hlo_compute_products_not_bf16": len(not_bf16),
        "hlo_pinned_ops_narrow": sum(narrow.values()),
        "hlo_sums_not_f32": sum(
            sum(1 for p in hits if p["result"] != "f32") + (not hits) for hits in sums.values()
        ),
        "products": by_scope,
    }


# --------------------------------------------------- one product at a time


def _program_sites(model_cfg, params: dict, inputs: dict) -> dict:
    """Every site of ``raft_train_mixed.SITES`` through the program's own
    product code at the policy's dtypes, one jitted call: ``conv2d`` (the
    form each kernel shape takes), a ``SplitConv2d`` gate built as the GRU
    builds it, ``build_corr_pyramid``."""
    from raft_ncup_tpu.nn.layers import SplitConv2d, conv2d
    from raft_ncup_tpu.ops.corr import build_corr_pyramid

    policy = model_cfg.precision_policy

    def products(params, inputs):
        out = {}
        for site, (kind, stride) in SITES.items():
            x = inputs[site]
            if kind == "volume":
                pyramid = build_corr_pyramid(x[0], x[1], 1, dtype=policy.corr_jnp)
                out[site] = pyramid.levels[0]
                continue
            p = site_params(params, site)
            cdt = policy.module_dtype or x.dtype
            kh, kw, width, features = p["kernel"].shape
            if kind == "gate":
                hidden = features
                gate = SplitConv2d(
                    features, (kh, kw), width, (hidden, hidden + model_cfg.context_dim), dtype=cdt
                )
                ctx = gate.apply({"params": p}, x[..., hidden : hidden + model_cfg.context_dim],
                                 method="context")
                rest = jnp.concatenate(
                    [x[..., :hidden], x[..., hidden + model_cfg.context_dim :]], axis=-1
                )
                out[site] = gate.apply({"params": p}, rest, ctx)
            else:
                out[site] = conv2d(
                    x.astype(cdt), p["kernel"].astype(cdt),
                    ((kh // 2, kh // 2), (kw // 2, kw // 2)),
                    site="site_check/" + site, stride=(stride, stride),
                )
        return out

    # Widened outside the jitted call: inside it, the compiler may take a
    # rounding that is widened again for no rounding at all.
    return {
        site: y.astype(jnp.float32) for site, y in jax.jit(products)(params, inputs).items()
    }


def _site_gaps(got: dict, want: dict) -> dict:
    return {
        site: float(jnp.linalg.norm((got[site] - want[site]).ravel())
                    / jnp.linalg.norm(want[site].ravel()))
        for site in want
    }


def _site_inputs(cell, params: dict) -> dict:
    h, w = cell.config["train"]["image_size"]
    return site_inputs(params, cell.seed, (h // 8, w // 8))


def _site_rows(cell, params: dict, got: dict) -> list:
    """``product_site_gap`` of the program, and the control
    ``accumulate_bf16`` in its place on the same inputs: its smallest site
    gap must read over the limit, or the row cannot see the accumulation."""
    inputs = _site_inputs(cell, params)
    want = site_products(params, inputs)
    program = _site_gaps(got, want)
    control = _site_gaps(site_products(params, inputs, drop="accumulate_bf16"), want)
    emit({"phase": "product_sites", "program": program, "accumulate_bf16": control})
    limit = cell.limit("product_site_gap")
    return [
        compared("product_site_gap", max(program.values()), limit),
        compared("accumulate_bf16_site_gap_negated", -min(control.values()), -limit),
    ]


# ----------------------------------------------------------------- the step


def _program(state, batch: dict, n_steps: int) -> dict:
    """What the check reads of the program: ``train_steps``'s three calls of
    the timed step, the program's products at the sites, and the HLO of the
    step function lowered on the run's own state and this batch (the module
    the compiler is handed for the timed executable, whatever the backend
    makes of it)."""
    from raft_ncup_tpu.parallel.multihost import device_put_batch

    run, cell = state["run"], state["cell"]
    got = base._program_steps(state, batch, n_steps)
    params = state["variables"]["params"]
    got["sites"] = _program_sites(run.model.cfg, params, _site_inputs(cell, params))
    lowered = run.step_fn.lower(
        run.state, device_put_batch(batch, None, None), jax.random.PRNGKey(0)
    )
    got["hlo"] = lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string()
    return got


def _gaps(cell, got: dict, ref: dict, prefix: str = "") -> list:
    """``train_steps``'s four rows and a fifth, the gap of NCUP's own
    parameters' gradient: the whole tree's gap is the encoders' and the update
    block's, and the worst module is fnet every time, so neither can see
    whether the upsampler ran as stated."""
    rows = base._compare(cell, got, ref)
    name = "grad_rel_gap_upsampler"
    gap = base._rel_gap(got["clipped"]["upsampler"], ref["clipped"]["upsampler"])
    rows.append(compared(name, gap, cell.limit(name)))
    return [{**r, "check": prefix + r["check"]} for r in rows]


def _counts(cell, report: dict, names) -> list:
    return [compared(name, report[name], cell.limit(name)) for name in names]


TALLY = ("pinned_sites_not_f32", "compute_sites_not_bf16", "f32_product_sites_gap")
HLO = ("hlo_compute_products_not_bf16", "hlo_pinned_ops_narrow", "hlo_sums_not_f32")


def _deciding_rows(cell, state, batch: dict, got: dict, precision: dict) -> list:
    """Every row of ``correct`` but the window's two: the step's gaps to the
    float32 reference, the module's counts, the tally's, the sites'."""
    n = int(cell.traffic["check_steps"])
    hlo = _hlo_report(cell, got["hlo"])
    emit({"phase": "hlo", **hlo})
    ref = state["reference"].steps(state["variables"], batch, n)
    return [
        *_gaps(cell, got, ref),
        *_counts(cell, hlo, HLO),
        *_counts(cell, precision, TALLY),
        *_site_rows(cell, state["variables"]["params"], got["sites"]),
    ]


def check(state, window: dict) -> list:
    cell, report = state["cell"], window["report"]
    batch = base._check_batch(state)
    got = _program(state, batch, int(cell.traffic["check_steps"]))
    return [
        compared("window_steps_vs_counter_gap",
                 abs(window["steps"] - report["train_steps_total"]), 0),
        compared("window_pairs_vs_counter_gap",
                 abs(window["pairs"] - report["train_pairs_total"]), 0),
        *_deciding_rows(cell, state, batch, got, report["precision"]),
    ]


def reading(cell, seconds: float) -> list:
    """The program's reading for ``readings.py``: the check's rows without a
    window, then the step's gaps to the mixed reference as information. With
    ``--model-precision f32`` the float32 program at jax's default matmul
    precision stands in the program's place, and the deciding rows are all."""
    _refuse_without_tally()
    f32_program = cell.config["model"]["precision"] == "f32"
    precision = (
        jax.default_matmul_precision("default") if f32_program else contextlib.nullcontext()
    )
    state = base._build(cell)
    try:
        batch, n = base._check_batch(state), int(cell.traffic["check_steps"])
        with precision:
            got = _program(state, batch, n)
        rows = _deciding_rows(cell, state, batch, got, _precision_report(cell))
        if f32_program:
            return rows
        emit({"phase": "deciding_rows", **{r["check"]: r["value"] for r in rows}})
        mixed = MixedTrainReference(cell.config["model"], cell.config["train"])
        return rows + _gaps(cell, got, mixed.steps(state["variables"], batch, n), "mixed.")
    finally:
        close(state)


def control(cell) -> list:
    """Every control of the configuration in the program's place, on the
    inputs ``check`` takes: its products at the sites first (every control's,
    before any step), then its step against the float32 reference."""
    inputs = base._inputs(cell)
    drops = list(cell.config["control"]["drop"])
    for drop in drops:
        if drop not in CONTROLS:
            raise NoResult(f"the mixed reference has no control {drop!r}: {CONTROLS}")
    params = inputs["variables"]["params"]
    sites = _site_inputs(cell, params)
    want = site_products(params, sites)
    rows = []
    for drop in drops:
        gaps = _site_gaps(site_products(params, sites, drop=drop), want)
        rows.append(compared(f"{drop}.product_site_gap", min(gaps.values()),
                             cell.limit("product_site_gap")))
        emit({"phase": "control_sites", "seed": cell.seed, "drop": drop, **gaps})
    batch, n = base._check_batch(inputs), int(cell.traffic["check_steps"])
    ref = inputs["reference"].steps(inputs["variables"], batch, n)
    for drop in drops:
        low = MixedTrainReference(cell.config["model"], cell.config["train"], drop=drop)
        got = low.steps(inputs["variables"], batch, n)
        emit({"phase": "control", "drop": drop})
        rows += _gaps(cell, got, ref, f"{drop}.")
    return rows
