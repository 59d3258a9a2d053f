"""graftlint (raft_ncup_tpu/analysis): one positive + one negative fixture
snippet per JGL rule, engine/allowlist behaviors, and the self-check that
puts the linter inside the tier-1 gate: the shipped tree lints clean.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

from raft_ncup_tpu.analysis.lint import (
    AllowlistError,
    load_allowlist,
    run_lint,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_snippet(tmp_path, source, name="snippet.py", axes=None, select=None):
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    result = run_lint(
        [str(path)],
        declared_axes=frozenset(axes) if axes is not None else None,
        select=select,
    )
    assert not result.parse_errors, result.parse_errors
    return result.findings


# --------------------------------------------------------------- JGL001


def test_jgl001_flags_host_sync_in_traced_code(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        import jax
        import numpy as np

        @jax.jit
        def step(state, batch):
            loss = (batch - state).sum()
            log_val = float(loss)      # per-step sync
            arr = np.asarray(loss)     # implicit pull
            scalar = loss.item()       # method pull
            return loss, log_val, arr, scalar
        """,
        select=["JGL001"],
    )
    assert [f.rule for f in findings] == ["JGL001"] * 3
    assert {f.qualname for f in findings} == {"step"}


def test_jgl001_ignores_host_side_code(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        import jax
        import numpy as np

        def host_loop(step_fn, state, batches):
            for batch in batches:
                state, metrics = step_fn(state, batch)
            return float(np.asarray(metrics))  # host side: fine
        """,
    )
    assert findings == []


def test_jgl001_traced_through_scan_and_assignment(tmp_path):
    """The repo's own pattern: body = jax.checkpoint(step);
    jax.lax.scan(body, ...) must mark `step` traced."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        def forward(xs, remat):
            def step(carry, x):
                v = carry + x
                bad = v.item()
                return v, bad

            body = step
            if remat:
                body = jax.checkpoint(step)
            return jax.lax.scan(body, 0.0, xs)
        """,
    )
    assert [f.rule for f in findings] == ["JGL001"]
    assert findings[0].qualname == "forward.step"


# --------------------------------------------------------------- JGL002


def test_jgl002_flags_undonated_state_step(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        def make_step(model):
            def step(state, batch, rng):
                return state, {}

            return jax.jit(step)
        """,
    )
    assert [f.rule for f in findings] == ["JGL002"]
    assert "donate" in findings[0].message


def test_jgl002_decorator_form_flagged(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(state, batch):
            return state
        """,
        select=["JGL002"],
    )
    assert [f.rule for f in findings] == ["JGL002"]


def test_jgl002_negative_donated_or_stateless(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        def make_steps(model):
            def step(state, batch, rng):
                return state, {}

            def eval_step(variables, image1, image2):
                return model(variables, image1, image2)

            donated = jax.jit(step, donate_argnums=0)
            eval_jit = jax.jit(eval_step)  # no state: nothing to donate
            return donated, eval_jit
        """,
        select=["JGL002"],
    )
    assert findings == []


def test_jgl002_sibling_scopes_do_not_cross_contaminate(tmp_path):
    """Same-named inner functions in sibling factories (the repo's
    make_train_step.step vs make_eval_step.step) must resolve per scope."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        def make_train_step():
            def step(state, batch):
                return state

            return jax.jit(step, donate_argnums=0)

        def make_eval_step():
            def step(variables, image1):
                return variables

            return jax.jit(step)
        """,
        select=["JGL002"],
    )
    assert findings == []


# --------------------------------------------------------------- JGL003


def test_jgl003_flags_trace_time_nondeterminism(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        import time
        import random
        import numpy as np
        import jax

        @jax.jit
        def step(x):
            noise = np.random.randn()        # baked at trace time
            jitter = random.random()         # baked at trace time
            t = time.time()                  # baked at trace time
            return x + noise + jitter + t
        """,
    )
    assert [f.rule for f in findings] == ["JGL003"] * 3


def test_jgl003_jax_random_is_exempt(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        import jax
        from jax import random

        @jax.jit
        def step(x, key):
            k1, k2 = random.split(key)
            return x + jax.random.normal(k1, x.shape), k2
        """,
        select=["JGL003"],
    )
    assert findings == []


# --------------------------------------------------------------- JGL004


def test_jgl004_flags_python_branch_on_traced_value(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def clamp(x):
            if jnp.any(x > 10):        # tracer branch
                x = jnp.clip(x, 0, 10)
            while (x < 0).all():       # tracer loop
                x = x + 1
            return x
        """,
    )
    assert [f.rule for f in findings] == ["JGL004"] * 2


def test_jgl004_static_branches_are_fine(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def forward(x, *, test_mode=False, iters=12):
            if test_mode:              # static python flag
                iters = 2
            if x.shape[0] % 8:         # static shape arithmetic
                raise ValueError("pad first")
            if jax.process_count() > 1:  # static runtime query
                pass
            return x * iters
        """,
        select=["JGL004"],
    )
    assert findings == []


# --------------------------------------------------------------- JGL005


def test_jgl005_flags_dtypeless_and_f64_in_ops(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        import jax.numpy as jnp
        import numpy as np

        KERNEL = jnp.asarray([0.25, 0.5, 0.25])   # dtype-less
        BAD = np.float64(1.0)                      # f64 in the core

        def widen(x):
            return x.astype("float64")             # string-spelled f64

        WIDE = jnp.asarray([1.0], dtype="float64")  # string-spelled f64
        """,
        name="ops/constants.py",
    )
    assert [f.rule for f in findings] == ["JGL005"] * 4


def test_jgl005_negative_explicit_dtype_and_out_of_scope(tmp_path):
    # explicit dtype in ops/: clean
    assert (
        lint_snippet(
            tmp_path,
            """
            import jax.numpy as jnp

            KERNEL = jnp.asarray([0.25, 0.5, 0.25], jnp.float32)
            IDX = jnp.asarray([1, 2], dtype=jnp.int32)
            """,
            name="ops/clean.py",
        )
        == []
    )
    # dtype-less outside ops//nn/: out of the rule's scope
    assert (
        lint_snippet(
            tmp_path,
            """
            import jax.numpy as jnp

            X = jnp.asarray([1.0, 2.0])
            """,
            name="drivers/free.py",
        )
        == []
    )


# --------------------------------------------------------------- JGL006


def test_jgl006_flags_undeclared_axis(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        from jax.sharding import PartitionSpec as P

        SPEC = P("data", "spatail")   # typo: silently replicates
        """,
        axes={"data", "spatial"},
    )
    assert [f.rule for f in findings] == ["JGL006"]
    assert "spatail" in findings[0].message


def test_jgl006_declared_axes_and_discovery(tmp_path):
    # declared axes (incl. tuple form and None) are clean
    assert (
        lint_snippet(
            tmp_path,
            """
            from jax.sharding import PartitionSpec as P

            A = P("data", "spatial", None)
            B = P(("data", "spatial"))
            C = P()
            """,
            axes={"data", "spatial"},
        )
        == []
    )
    # axis names are discovered from a Mesh() declaration in the lint set
    # (fresh subdir: the snippet above declared data/spatial axes)
    disc = tmp_path / "disc"
    disc.mkdir()
    (disc / "mesh.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import Mesh

            def make(devices):
                return Mesh(devices, ("rows", "cols"))
            """
        )
    )
    (disc / "user.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P

            GOOD = P("rows")
            BAD = P("data")
            """
        )
    )
    result = run_lint([str(disc)])
    assert result.declared_axes == frozenset({"rows", "cols"})
    assert [f.rule for f in result.findings] == ["JGL006"]
    assert "'data'" in result.findings[0].message


def test_jgl006_silent_without_declaration(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        from jax.sharding import PartitionSpec as P

        SPEC = P("whatever")
        """,
        axes=set(),
    )
    assert findings == []


def test_jgl006_standalone_subsystem_lint_uses_production_axes(tmp_path):
    """Linting inference//serving//streaming/ WITHOUT parallel/mesh.py in
    the set must still judge PartitionSpec axes against the production
    declarer's axes (lint.production_declared_axes fallback): a typo'd
    axis in a serving module silently replicates — the exact JGL006
    hazard — and the pre-fallback engine went silent on standalone
    lints."""
    from raft_ncup_tpu.analysis.lint import run_lint

    for sub in ("inference", "serving", "streaming"):
        d = tmp_path / sub
        d.mkdir()
        (d / "sharded.py").write_text(
            textwrap.dedent(
                """
                from jax.sharding import PartitionSpec as P

                BAD = P("spatail")
                GOOD = P("data", "spatial")
                """
            )
        )
        result = run_lint([str(d)])
        assert result.declared_axes >= {"data", "spatial"}, sub
        assert [f.rule for f in result.findings] == ["JGL006"], sub
        assert "spatail" in result.findings[0].message


def test_jgl006_standalone_subsystem_negative_declared_axes(tmp_path):
    """The negative half: standalone subsystem files whose PartitionSpecs
    name only declared production axes lint clean under the fallback."""
    from raft_ncup_tpu.analysis.lint import run_lint

    d = tmp_path / "serving"
    d.mkdir()
    (d / "ok.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import NamedSharding, PartitionSpec as P

            def shardings(mesh):
                return {
                    "image1": NamedSharding(mesh, P("data", "spatial")),
                    "table": NamedSharding(mesh, P("data")),
                    "repl": NamedSharding(mesh, P()),
                }
            """
        )
    )
    result = run_lint([str(d)])
    assert result.findings == []


def test_jgl006_discovers_conditional_axis_tuple(tmp_path):
    """Declared-axes discovery descends conditional-expression axis
    tuples — ``Mesh(arr, (..., "pipe") if pipe > 1 else (...))``
    declares a third axis in ONE call (both branches count as
    declarations), so 'pipe' must be usable in PartitionSpecs without a
    JGL006 false positive, while a typo'd axis still fires."""
    from raft_ncup_tpu.analysis.lint import run_lint

    d = tmp_path / "pipe_ok"
    d.mkdir()
    (d / "mesh.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import Mesh

            def make(arr, pipe):
                return Mesh(
                    arr,
                    ("data", "spatial", "pipe")
                    if pipe > 1
                    else ("data", "spatial"),
                )
            """
        )
    )
    (d / "use.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P

            STATE = P("pipe")
            IMG = P("data", "spatial")
            """
        )
    )
    result = run_lint([str(d)])
    assert result.declared_axes == frozenset({"data", "spatial", "pipe"})
    assert result.findings == []

    # negative half: an axis in NEITHER branch still fires
    bad = tmp_path / "pipe_bad"
    bad.mkdir()
    (bad / "mesh.py").write_text((d / "mesh.py").read_text())
    (bad / "use.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P

            STATE = P("pip")   # typo: silently replicates
            """
        )
    )
    result = run_lint([str(bad)])
    assert [f.rule for f in result.findings] == ["JGL006"]
    assert "pip" in result.findings[0].message


def test_jgl006_production_axes_are_data_and_spatial():
    """The real make_mesh feeds the production fallback set: exactly the
    two axes it can build, so a PartitionSpec naming any other axis in
    a standalone subsystem lint run is a finding."""
    from raft_ncup_tpu.analysis.lint import production_declared_axes

    assert production_declared_axes() == frozenset({"data", "spatial"})


# --------------------------------------------------------------- JGL007


def test_jgl007_flags_swallowed_exceptions(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        def load(path):
            try:
                return open(path).read()
            except Exception:
                pass

        def drain(q):
            while True:
                try:
                    return q.get_nowait()
                except:
                    continue
        """,
        name="data/bad.py",
    )
    assert [f.rule for f in findings] == ["JGL007"] * 2
    assert {f.qualname for f in findings} == {"load", "drain"}


def test_jgl007_negative_handled_or_narrow(tmp_path):
    """Re-raised, logged/accounted, or narrow handlers are all fine —
    the rule only hunts silent broad swallows."""
    findings = lint_snippet(
        tmp_path,
        """
        import sys

        def save(fn):
            try:
                fn()
            except Exception as e:
                print(f"save failed: {e}", file=sys.stderr)
                raise

        def close(handle):
            try:
                handle.close()
            except OSError:
                pass  # narrow: an expected, decided-on drop

        def teardown(handle, stats):
            try:
                handle.close()
            except Exception as e:
                stats.record(e)  # accounted
        """,
        name="training/ok.py",
    )
    assert findings == []


def test_jgl007_out_of_scope_paths_exempt(tmp_path):
    """The same swallow outside resilience//training//data/ is not this
    rule's business (drivers and analysis code have their own idioms)."""
    findings = lint_snippet(
        tmp_path,
        """
        def f(x):
            try:
                return x()
            except Exception:
                pass
        """,
        name="drivers/free.py",
    )
    assert findings == []


# --------------------------------------------------------------- JGL009


def test_jgl009_flags_inline_dtype_literals_on_hot_path(tmp_path):
    """Raw jnp dtype literals in models//nn//inference/ function bodies
    bypass the precision policy — both the narrow (bfloat16) and the
    wide (float32) direction are dtype decisions the policy must own."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax.numpy as jnp

        def forward(policy, x, coords):
            feats = x.astype(jnp.bfloat16)       # inline narrow
            coords = coords.astype(jnp.float32)  # inline wide
            acc = jnp.zeros((2,), jnp.float32)   # inline wide
            return feats, coords, acc
        """,
        name="models/hotpath.py",
    )
    assert [f.rule for f in findings] == ["JGL009"] * 3
    assert {f.qualname for f in findings} == {"forward"}


def test_jgl009_sanctioned_routings_are_clean(tmp_path):
    """The three sanctioned shapes: policy reads, flax class-attribute
    defaults, and named module-level constants — plus out-of-scope paths
    (ops/ keeps JGL005's narrower dtype-hygiene rule)."""
    assert (
        lint_snippet(
            tmp_path,
            """
            import jax.numpy as jnp
            from typing import Any

            PARAM_DTYPE = jnp.float32  # mirrors PrecisionPolicy.param_jnp

            class Conv:
                dtype: Any = jnp.float32  # policy-settable knob

                def __call__(self, policy, x):
                    y = x.astype(self.dtype or x.dtype)
                    return y.astype(policy.compute_jnp), PARAM_DTYPE
            """,
            name="nn/clean.py",
        )
        == []
    )
    assert (
        lint_snippet(
            tmp_path,
            """
            import jax.numpy as jnp

            def widen(x):
                return x.astype(jnp.float32)
            """,
            name="ops/free.py",
            select=["JGL009"],
        )
        == []
    )


def test_jgl009_sentinel_module_in_scope(tmp_path):
    """resilience/anomaly.py is scoped in deliberately: the sentinel's
    f32 arithmetic is policy-pinned, so its literals must be VISIBLE
    (allowlisted with justification), not invisible."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax.numpy as jnp

        def guard(x):
            return jnp.float32(0.5) * x
        """,
        name="resilience/anomaly.py",
        select=["JGL009"],
    )
    assert [f.rule for f in findings] == ["JGL009"]


# --------------------------------------------------------------- JGL010


def test_jgl010_flags_jax_and_pulls_in_observability(tmp_path):
    """Telemetry is host-only: jax imports, jax.* calls, numpy pulls,
    and .item()/.tolist() inside observability/ all violate the
    no-device-access / no-added-sync constraint."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax
        import numpy as np

        def record(registry, value):
            host = jax.device_get(value)       # pull inside telemetry
            arr = np.asarray(value)            # implicit pull
            scalar = value.item()              # method pull
            registry.counter("x").inc(host + arr.sum() + scalar)
        """,
        name="observability/bad.py",
        select=["JGL010"],
    )
    assert [f.rule for f in findings] == ["JGL010"] * 4
    # The import finding is module-level; the pulls are inside record().
    assert "record" in {f.qualname for f in findings}


def test_jgl010_from_jax_import_flagged(tmp_path):
    findings = lint_snippet(
        tmp_path,
        """
        from jax import profiler
        """,
        name="observability/spans.py",
        select=["JGL010"],
    )
    assert [f.rule for f in findings] == ["JGL010"]


def test_jgl010_host_only_telemetry_is_clean(tmp_path):
    """The package's real shape — stdlib locks, clocks, math on host
    scalars — is clean, and the same code outside observability/ is not
    this rule's business."""
    clean = """
        import threading
        import time

        def observe(hist, seconds):
            hist.observe_ms(float(seconds) * 1000.0)

        def snapshot(metrics):
            return {k: m.value for k, m in sorted(metrics.items())}
        """
    assert lint_snippet(
        tmp_path, clean, name="observability/good.py", select=["JGL010"]
    ) == []
    pulls_elsewhere = """
        import jax

        def boundary(x):
            return jax.device_get(x)  # a producer's sanctioned pull
        """
    assert lint_snippet(
        tmp_path, pulls_elsewhere, name="serving/free.py",
        select=["JGL010"],
    ) == []


@pytest.mark.parametrize(
    "module", ["health.py", "slo.py", "flight.py"]
)
def test_jgl010_covers_the_consumer_half_modules(tmp_path, module):
    """The PR 12 consumer modules (health state machine, SLO burn-rate
    engine, flight recorder) sit under the same host-only contract as
    the producers: a jax import or device pull inside any of them is a
    finding, and their real shapes (stdlib state machines, counter
    deltas, atomic JSON writes) are clean. Zero allowlist entries."""
    dirty = """
        import jax

        def evaluate(registry, value):
            return float(jax.device_get(value))  # sync inside telemetry
        """
    findings = lint_snippet(
        tmp_path, dirty, name=f"observability/{module}",
        select=["JGL010"],
    )
    assert [f.rule for f in findings] == ["JGL010"] * 2
    clean = """
        import json
        import os
        import time

        ALLOWED = {"ready": {"degraded", "draining"}}

        def transition(state, to):
            return to if to in ALLOWED.get(state, set()) else state

        def burn(bad, total, budget):
            return (bad / total) / budget if total else 0.0

        def atomic_write(path, payload):
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(payload, fh)
            os.replace(tmp, path)
        """
    assert lint_snippet(
        tmp_path, clean, name=f"observability/{module}",
        select=["JGL010"],
    ) == []


def test_jgl010_covers_aggregate_module(tmp_path):
    """The fleet trace/registry aggregator (PR 14) is the offline tool
    most tempted to import jax 'for convenience' — it sits under the
    same host-only contract, pinned explicitly: a jax import or device
    pull inside observability/aggregate.py is a finding; its real shape
    (json merges, clock-offset arithmetic on host floats) is clean."""
    dirty = """
        import jax

        def merge(records, value):
            return records + [float(jax.device_get(value))]
        """
    findings = lint_snippet(
        tmp_path, dirty, name="observability/aggregate.py",
        select=["JGL010"],
    )
    assert [f.rule for f in findings] == ["JGL010"] * 2
    clean = """
        import json
        import os

        def translate(records, offset_s):
            return [
                {**r, "t": r["t_s"] - offset_s}
                for r in records if "t_s" in r
            ]

        def read_tolerant(path):
            out, skipped = [], 0
            with open(path) as fh:
                for line in fh:
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        skipped += 1
            return out, skipped
        """
    assert lint_snippet(
        tmp_path, clean, name="observability/aggregate.py",
        select=["JGL010"],
    ) == []


def test_jgl010_fleet_trace_header_must_stay_optional(tmp_path):
    """Wire-compat contract: the frame schema's trace-context field is
    OPTIONAL — a mandatory `header[\"trace\"]` READ in fleet/ would make
    old peers' frames unparsable by new fleet code, so it is a finding;
    reading with .get and WRITING the field (a producer knows its own
    schema) are clean, as is the same subscript outside fleet/."""
    dirty = """
        def adopt(header):
            ctx = header["trace"]  # mandatory read: old frames crash
            return ctx
        """
    findings = lint_snippet(
        tmp_path, dirty, name="fleet/router.py", select=["JGL010"],
    )
    assert [f.rule for f in findings] == ["JGL010"]
    assert "optional" in findings[0].message.lower()
    clean = """
        def dispatch(header, ctx):
            header["trace"] = ctx          # producer write: fine
            return header.get("trace")     # tolerant read: fine
        """
    assert lint_snippet(
        tmp_path, clean, name="fleet/router.py", select=["JGL010"],
    ) == []
    elsewhere = """
        def adopt(header):
            return header["trace"]  # not fleet/ wire code
        """
    assert lint_snippet(
        tmp_path, elsewhere, name="serving/server.py", select=["JGL010"],
    ) == []


# ------------------------------------------------------------- allowlist


def test_jgl008_flags_per_batch_pulls_in_eval_loop(tmp_path):
    """Per-iteration host pulls in the eval hot loop: the exact bug class
    the async eval pipeline removed (per-batch full-field device_get)."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        def validate(fwd, batches):
            total = 0.0
            for batch in batches:
                acc = fwd(batch)
                total += jax.device_get(acc)[0]
            return total

        def drain(q):
            while q:
                q.pop().item()

        def collect(accs):
            return [a.tolist() for a in accs]
        """,
        name="inference/bad.py",
    )
    assert [f.rule for f in findings] == ["JGL008"] * 3
    assert {f.qualname for f in findings} == {"validate", "drain", "collect"}


def test_jgl008_negative_window_pull_throttle_and_nested_def(tmp_path):
    """Sanctioned shapes: ONE pull at the window boundary (after the
    loop), a bounded block_until_ready (sync, not transfer), and a pull
    inside a callback merely DEFINED in the loop (runs off-loop, e.g. on
    the AsyncDrain worker)."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        def validate(fwd, batches, throttle):
            acc = None
            for batch in batches:
                acc = fwd(batch)
                jax.block_until_ready(acc)
            return jax.device_get(acc)

        def submit_all(drain, outs):
            for out in outs:
                def write_cb():
                    return jax.device_get(out)
                drain.submit(write_cb)
        """,
        name="raft_ncup_tpu/evaluation.py",
    )
    assert findings == []


def test_jgl008_serving_dispatcher_in_scope(tmp_path):
    """The serving dispatcher is the same hot loop facing an open-loop
    stream: a per-batch pull on the dispatch thread re-serializes every
    batch with d2h transfer — the AsyncDrain worker owns the pull."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        def dispatch(queue, fwd):
            while queue:
                batch = queue.pop()
                flow = fwd(batch)
                return_to_client(jax.device_get(flow))
        """,
        name="raft_ncup_tpu/serving/server.py",
    )
    assert [f.rule for f in findings] == ["JGL008"]
    assert findings[0].qualname == "dispatch"


def test_jgl008_streaming_dispatcher_in_scope(tmp_path):
    """The streaming engine's dispatch loop is in scope: per-stream
    recurrent state lives in the device slot table precisely so nothing
    needs pulling between frames — a per-batch pull there reintroduces
    the serialization the subsystem deletes."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        def dispatch(queue, step, table):
            while queue:
                batch = queue.pop()
                table, flow, bad = step(table, batch)
                notify(jax.device_get(bad))
        """,
        name="raft_ncup_tpu/streaming/engine.py",
    )
    assert [f.rule for f in findings] == ["JGL008"]
    assert findings[0].qualname == "dispatch"


def test_jgl008_streaming_negative_device_resident_loop(tmp_path):
    """The sanctioned streaming shape: the slot-table carry stays on
    device across iterations, the bounded throttle syncs without
    transferring, and the flow+flags pull rides a callback that runs on
    the AsyncDrain worker (defined in the loop, executed off it)."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        def dispatch(queue, step, table, throttle, drain):
            while queue:
                batch = queue.pop()
                table, flow, bad = step(table, batch)
                jax.block_until_ready(flow)

                def deliver(host):
                    complete(host)

                drain.submit((flow, bad), deliver)
        """,
        name="raft_ncup_tpu/streaming/engine.py",
    )
    assert findings == []


def test_jgl008_out_of_scope_paths_exempt(tmp_path):
    """The same per-iteration pull outside inference//evaluation.py is
    JGL001's business (when traced) or legitimate driver code."""
    findings = lint_snippet(
        tmp_path,
        """
        import jax

        def summarize(metrics_list):
            return [jax.device_get(m) for m in metrics_list]
        """,
        name="training/logger.py",
        select=["JGL008"],
    )
    assert findings == []


def test_allowlist_suppresses_with_justification(tmp_path):
    snippet = tmp_path / "mod.py"
    snippet.write_text(
        textwrap.dedent(
            """
            import jax

            @jax.jit
            def step(x):
                return float(x)
            """
        )
    )
    allow = tmp_path / "allow.txt"
    allow.write_text("mod.py::JGL001::step  # audited: test fixture\n")
    result = run_lint([str(snippet)], allowlist_path=str(allow))
    assert result.findings == []
    assert len(result.suppressed) == 1
    assert result.stale_entries == []


def test_allowlist_requires_justification(tmp_path):
    allow = tmp_path / "allow.txt"
    allow.write_text("mod.py::JGL001::step\n")
    with pytest.raises(AllowlistError, match="justification"):
        load_allowlist(str(allow))


def test_allowlist_stale_entry_reported(tmp_path):
    snippet = tmp_path / "clean.py"
    snippet.write_text("X = 1\n")
    allow = tmp_path / "allow.txt"
    allow.write_text("clean.py::JGL001::*  # obsolete\n")
    result = run_lint([str(snippet)], allowlist_path=str(allow))
    assert len(result.stale_entries) == 1


def test_allowlist_not_stale_when_rule_deselected(tmp_path):
    """`--select` must not mark entries of skipped rules stale — lint.sh
    --select <rule> would otherwise fail spuriously under
    --strict-allowlist."""
    snippet = tmp_path / "mod.py"
    snippet.write_text(
        textwrap.dedent(
            """
            import jax

            @jax.jit
            def step(x):
                return float(x)
            """
        )
    )
    allow = tmp_path / "allow.txt"
    allow.write_text("mod.py::JGL001::step  # audited: test fixture\n")
    result = run_lint(
        [str(snippet)], allowlist_path=str(allow), select=["JGL005"]
    )
    assert result.stale_entries == []  # JGL001 never ran: undecidable
    # ...but with the rule selected and the finding gone, it IS stale
    clean = tmp_path / "clean"
    clean.mkdir()
    (clean / "mod.py").write_text("X = 1\n")
    result = run_lint(
        [str(clean / "mod.py")], allowlist_path=str(allow), select=["JGL001"]
    )
    assert len(result.stale_entries) == 1


def test_parse_error_is_reported_not_raised(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    result = run_lint([str(bad)])
    assert len(result.parse_errors) == 1


# ------------------------------------------- JGL007/JGL010 fleet scope


def test_jgl010_fleet_scope_flags_jax_and_pulls(tmp_path):
    """The fleet control plane shares observability/'s host-only
    contract (zero allowlist entries): a router that can touch a device
    array can add a sync to every request it routes."""
    dirty = """
        import jax
        import numpy as np

        def route(request, value):
            flow = np.asarray(value)        # implicit pull in the router
            return jax.device_get(flow)     # explicit device access
        """
    findings = lint_snippet(
        tmp_path, dirty, name="fleet/router.py", select=["JGL010"]
    )
    assert [f.rule for f in findings] == ["JGL010"] * 3


def test_jgl010_fleet_scope_wire_idioms_are_clean(tmp_path):
    """The fleet's real shape — stdlib sockets/json/signals plus
    numpy frombuffer/tobytes on HOST arrays — is clean: the rule bans
    the pull shapes (asarray/array/.item()/.tolist()), not numpy."""
    clean = """
        import json
        import socket
        import struct

        import numpy as np

        def send(sock, header, arr):
            blob = json.dumps(header).encode()
            sock.sendall(struct.pack(">I", len(blob)) + blob
                         + arr.tobytes())

        def recv_payload(buf, dtype, shape):
            return np.frombuffer(buf, dtype=dtype).reshape(shape)
        """
    assert lint_snippet(
        tmp_path, clean, name="fleet/wire.py", select=["JGL010"]
    ) == []


def test_jgl007_fleet_scope_supervisor_must_not_eat_deaths(tmp_path):
    """A supervisor that silently eats a child's death is the exact
    failure mode the fleet tier exists to prevent — JGL007's swallowed-
    exception hunt covers fleet/ too."""
    dirty = """
        def poll(children):
            for child in children:
                try:
                    child.check()
                except Exception:
                    pass  # a dead replica vanishes silently
        """
    findings = lint_snippet(
        tmp_path, dirty, name="fleet/replica.py", select=["JGL007"]
    )
    assert [f.rule for f in findings] == ["JGL007"]
    accounted = """
        def poll(children, stats):
            for child in children:
                try:
                    child.check()
                except Exception as e:
                    stats.note_death(child, e)  # counted, never silent

        def close(sock):
            try:
                sock.close()
            except OSError:
                pass  # narrow: a decided-on drop, out of scope
        """
    assert lint_snippet(
        tmp_path, accounted, name="fleet/replica.py", select=["JGL007"]
    ) == []


def test_jgl010_autoscaler_scope_control_loop_is_host_only(tmp_path):
    """The autoscaler decides fleet topology from healthz dicts and
    router counters — a control loop that can pull a device array can
    stall every replica it sizes. fleet/ directory scope covers the
    new module with zero allowlist entries."""
    dirty = """
        import jax
        import numpy as np

        def occupancy(replica_outputs):
            flows = [np.asarray(o) for o in replica_outputs]  # pull
            return float(jax.device_get(flows[0]).mean())
        """
    findings = lint_snippet(
        tmp_path, dirty, name="fleet/autoscaler.py", select=["JGL010"]
    )
    assert findings and all(f.rule == "JGL010" for f in findings)
    clean = """
        import threading
        import time

        def tick(handles, router, cfg):
            ups = [h for h in handles if h.state == "up"]
            cap = len(ups) * cfg.max_inflight_per_replica
            used = sum(router.inflight_of(h.index) for h in ups)
            paging = [
                p for h in ups
                for p in ((h.last_healthz or {}).get("slo") or {})
                .get("paging", [])
            ]
            return {"occupancy": used / cap if cap else 1.0,
                    "paging": paging, "t": time.monotonic()}
        """
    assert lint_snippet(
        tmp_path, clean, name="fleet/autoscaler.py", select=["JGL010"]
    ) == []


def test_jgl007_host_supervisor_must_not_eat_agent_errors(tmp_path):
    """A manager that silently eats a host agent's RPC failure turns a
    dead host into a vanished host — the staleness/fencing contract
    only works if every agent error is counted. JGL007 covers the new
    host_supervisor module via the fleet/ scope."""
    dirty = """
        def poll_hosts(agents):
            snapshots = {}
            for host, agent in agents.items():
                try:
                    snapshots[host] = agent.call("snapshot")
                except Exception:
                    continue  # silent: the host just disappears
            return snapshots
        """
    findings = lint_snippet(
        tmp_path, dirty, name="fleet/host_supervisor.py",
        select=["JGL007"],
    )
    assert [f.rule for f in findings] == ["JGL007"]
    accounted = """
        def poll_hosts(agents, tel, missed):
            snapshots = {}
            for host, agent in agents.items():
                try:
                    snapshots[host] = agent.call("snapshot")
                except Exception as e:
                    missed[host] = missed.get(host, 0) + 1
                    tel.event("fleet_host_poll_miss", host=host,
                              error=repr(e))  # counted, never silent
            return snapshots

        def fence_sock(sock):
            try:
                sock.close()
            except OSError:
                pass  # narrow: a decided-on drop, out of scope
        """
    assert lint_snippet(
        tmp_path, accounted, name="fleet/host_supervisor.py",
        select=["JGL007"],
    ) == []


def test_jgl010_host_supervisor_fencing_idioms_are_clean(tmp_path):
    """The host-supervisor's real vocabulary — signals, /proc reads,
    wire sockets, healthz JSON — is exactly the host-only shape JGL010
    protects; the rule must not cry wolf on it."""
    clean = """
        import os
        import signal

        def fence(pids):
            reaped = []
            for pid in pids:
                try:
                    os.kill(pid, signal.SIGKILL)
                    reaped.append(pid)
                except ProcessLookupError:
                    reaped.append(pid)  # already gone counts as fenced
            return reaped

        def alive(pid):
            try:
                with open(f"/proc/{pid}/stat") as fh:
                    stat = fh.read()
            except OSError:
                return False
            return stat.rpartition(")")[2].split()[0] != "Z"
        """
    assert lint_snippet(
        tmp_path, clean, name="fleet/host_supervisor.py",
        select=["JGL010"],
    ) == []


def lint_files(tmp_path, files, select=None):
    """Multi-file fixture helper for the whole-program rules: write each
    ``rel_path -> source`` pair under tmp_path and lint the directory."""
    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    result = run_lint([str(tmp_path)], select=select)
    assert not result.parse_errors, result.parse_errors
    return result.findings


# --------------------------------------------------------------- JGL011


def test_jgl011_flags_unlocked_read_of_guarded_attr(tmp_path):
    """An attr written under the class lock in one method and read bare
    in another is exactly the race the fleet tier keeps hitting — the
    finding names BOTH sites."""
    findings = lint_files(
        tmp_path,
        {
            "fleet/reg.py": """
            import threading

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = {}

                def add(self, key, value):
                    with self._lock:
                        self._items[key] = value

                def peek(self, key):
                    return self._items.get(key)   # unlocked read
            """,
        },
        select=["JGL011"],
    )
    assert [f.rule for f in findings] == ["JGL011"]
    f = findings[0]
    assert f.qualname == "peek"
    assert "Registry._items" in f.message
    assert "written under the class lock" in f.message
    assert "[add]" in f.message  # the guarded-write site is named too


def test_jgl011_locked_reads_and_always_locked_helpers_clean(tmp_path):
    """The discipline the fixed fleet code follows is clean: every
    access under the lock, __init__ exempt, and a private helper whose
    call sites all hold the lock inherits the guard (the always-locked
    fixpoint — no false positive on the helper's bare reads)."""
    findings = lint_files(
        tmp_path,
        {
            "fleet/reg.py": """
            import threading

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = {}

                def add(self, key, value):
                    with self._lock:
                        self._items[key] = value

                def peek(self, key):
                    with self._lock:
                        return self._items.get(key)

                def _locked_size(self):
                    return len(self._items)   # guarded via callers

                def size(self):
                    with self._lock:
                        return self._locked_size()
            """,
        },
        select=["JGL011"],
    )
    assert findings == []


def test_jgl011_scope_is_fleet_and_observability_only(tmp_path):
    """The same racy shape outside fleet//observability/ is not this
    rule's business (single-threaded modules own their own state)."""
    findings = lint_files(
        tmp_path,
        {
            "inference/reg.py": """
            import threading

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = {}

                def add(self, key, value):
                    with self._lock:
                        self._items[key] = value

                def peek(self, key):
                    return self._items.get(key)
            """,
        },
        select=["JGL011"],
    )
    assert findings == []


# --------------------------------------------------------------- JGL012


def test_jgl012_flags_bare_subscript_and_both_drift_halves(tmp_path):
    """Across the two protocol ends: a bare-subscript read (optional-
    field contract), a key written but never read, and a key read but
    never written are each findings."""
    findings = lint_files(
        tmp_path,
        {
            "fleet/worker.py": """
            def handle(header):
                value = header["payload"]      # bare subscript
                if header.get("kind") != "job":
                    return None
                return value

            def reply_ok(rid):
                reply = {"kind": "ok", "orphan_field": rid}
                return reply
            """,
            "serve.py": """
            def consume(header):
                return header.get("ghost_field")
            """,
        },
        select=["JGL012"],
    )
    assert findings and all(f.rule == "JGL012" for f in findings)
    messages = [f.message for f in findings]
    assert any(
        "'payload'" in m and "bare" in m for m in messages
    ), messages
    assert any(
        "'orphan_field'" in m and "never read" in m for m in messages
    ), messages
    assert any(
        "'ghost_field'" in m and "never written" in m for m in messages
    ), messages


def test_jgl012_matched_keys_and_carveouts_are_clean(tmp_path):
    """A produced-and-consumed key is clean; 'kind' (the one REQUIRED
    field) may be subscripted; the 'trace' key inside fleet/ belongs to
    JGL010's carve-out, not this rule."""
    findings = lint_files(
        tmp_path,
        {
            "fleet/worker.py": """
            def reply_ok(rid, header, ctx):
                kind = header["kind"]          # required field: honest
                header["trace"] = ctx
                trace = header["trace"]        # JGL010's carve-out
                reply = {"kind": "ok", "result": rid}
                return reply, kind, trace
            """,
            "serve.py": """
            def consume(header):
                return header.get("result"), header.get("kind")
            """,
        },
        select=["JGL012"],
    )
    assert findings == []


def test_jgl012_drift_needs_both_protocol_ends(tmp_path):
    """A standalone lint of one directory cannot distinguish drift from
    out-of-scope use: without serve.py in the linted set, the drift
    halves stay silent (the per-site bare-subscript check still runs)."""
    findings = lint_files(
        tmp_path,
        {
            "fleet/worker.py": """
            def reply_ok(rid):
                return {"kind": "ok", "half_seen": rid}
            """,
        },
        select=["JGL012"],
    )
    assert findings == []


# --------------------------------------------------------------- JGL013


def test_jgl013_flags_stragglers_unregistered_and_dead_knobs(tmp_path):
    """All three halves: a direct os.environ read of a knob-prefixed
    name (resolved through a module constant), a knob_* getter naming
    an undeclared knob, and a registered knob nobody reads."""
    findings = lint_files(
        tmp_path,
        {
            "utils/knobs.py": """
            KNOBS = (
                Knob("RAFT_NCUP_ALPHA", "str", "a", "alpha knob"),
                Knob("RAFT_NCUP_DEAD", "str", "d", "dead knob"),
            )
            """,
            # The unread-knob half is gated on the full driver scope.
            "train.py": "",
            "serve.py": "",
            "evaluate.py": """
            import os
            from raft_ncup_tpu.utils.knobs import knob_str

            ALPHA_ENV = "RAFT_NCUP_ALPHA"

            def f():
                direct = os.environ.get(ALPHA_ENV)      # straggler
                good = knob_str("RAFT_NCUP_ALPHA")
                bad = knob_str("RAFT_NCUP_GHOST")       # undeclared
                benign = os.environ.get("PATH")         # not a knob
                return direct, good, bad, benign
            """,
        },
        select=["JGL013"],
    )
    assert [f.rule for f in findings] == ["JGL013"] * 3
    messages = [f.message for f in findings]
    assert any(
        "direct os.environ read" in m and "'RAFT_NCUP_ALPHA'" in m
        for m in messages
    ), messages
    assert any("'RAFT_NCUP_GHOST'" in m for m in messages), messages
    assert any(
        "'RAFT_NCUP_DEAD'" in m and "ever reads it" in m for m in messages
    ), messages


def test_jgl013_registered_reads_and_non_knob_names_clean(tmp_path):
    """Getter reads of registered names are the sanctioned shape;
    non-prefixed env vars (PATH, JAX_PLATFORMS) are not knobs."""
    findings = lint_files(
        tmp_path,
        {
            "utils/knobs.py": """
            KNOBS = (
                Knob("RAFT_NCUP_ALPHA", "str", "a", "alpha knob"),
            )
            """,
            "mod.py": """
            import os
            from raft_ncup_tpu.utils.knobs import knob_str

            def f():
                good = knob_str("RAFT_NCUP_ALPHA")
                benign = os.environ.get("PATH")
                internal = os.environ.get("JAX_PLATFORMS")
                return good, benign, internal
            """,
        },
        select=["JGL013"],
    )
    assert findings == []


def test_jgl013_unread_half_needs_registry_and_drivers_in_scope(tmp_path):
    """A package-only lint sees the registry but not the driver entry
    points where most readers live — it cannot call a knob dead (the
    same scope-completeness gate JGL012 applies to drift). The other
    two halves still run per-site."""
    findings = lint_files(
        tmp_path,
        {
            "utils/knobs.py": """
            KNOBS = (
                Knob("RAFT_NCUP_ELSEWHERE", "str", "x",
                     "read only by an out-of-scope driver"),
            )
            """,
        },
        select=["JGL013"],
    )
    assert findings == []


def test_jgl013_runtime_registry_matches_static_declarations():
    """The shipped registry is importable pure-stdlib, every declared
    knob resolves through get(), and unregistered names raise — the
    runtime half that covers dynamic getter names JGL013 cannot see."""
    from raft_ncup_tpu.utils import knobs

    assert len(knobs.KNOBS) == len({k.name for k in knobs.KNOBS})
    for knob in knobs.KNOBS:
        assert knobs.get(knob.name) is knob
    with pytest.raises(KeyError):
        knobs.get("RAFT_NCUP_NOT_A_KNOB")


# ------------------------------------------------- astutil name resolution


def test_collect_aliases_edge_cases():
    import ast as _ast

    from raft_ncup_tpu.analysis.astutil import collect_aliases

    tree = _ast.parse(textwrap.dedent("""
        import numpy as np
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from os import path
        import threading
    """))
    aliases = collect_aliases(tree)
    assert aliases["np"] == "numpy"
    assert aliases["jnp"] == "jax.numpy"
    assert aliases["P"] == "jax.sharding.PartitionSpec"
    assert aliases["path"] == "os.path"
    assert aliases["threading"] == "threading"


def test_dotted_name_resolution_edge_cases():
    import ast as _ast

    from raft_ncup_tpu.analysis.astutil import (
        collect_aliases,
        dotted_name,
    )

    tree = _ast.parse("import numpy as np")
    aliases = collect_aliases(tree)

    def expr(src):
        return _ast.parse(src).body[0].value

    # Aliased import expands the leading segment only.
    assert dotted_name(expr("np.random.default_rng"), aliases) == (
        "numpy.random.default_rng"
    )
    # Attribute chains through self stay rooted at the literal name.
    assert dotted_name(expr("self.tel.registry.counter"), {}) == (
        "self.tel.registry.counter"
    )
    # Dynamic bases (subscripts, calls) are honestly unresolvable.
    assert dotted_name(expr("items[0].attr"), {}) is None
    assert dotted_name(expr("get_tel().inc"), {}) is None


def test_qualname_nested_functions():
    import ast as _ast

    from raft_ncup_tpu.analysis.astutil import attach_parents, qualname

    tree = _ast.parse(textwrap.dedent("""
        def outer():
            def inner():
                return probe
    """))
    attach_parents(tree)
    probe = next(
        n for n in _ast.walk(tree)
        if isinstance(n, _ast.Name) and n.id == "probe"
    )
    assert qualname(probe) == "outer.inner"


# -------------------------------------------------------- JSON output


def test_cli_json_output_schema(tmp_path):
    """`--format json` is a STABLE machine surface: top-level keys,
    per-finding keys, and the suppressed flag are pinned here so CI
    tooling can diff lint runs across versions."""
    import json as _json

    bad = tmp_path / "fleet" / "reg.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent("""
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = {}

            def add(self, key, value):
                with self._lock:
                    self._items[key] = value

            def peek(self, key):
                return self._items.get(key)
    """))
    proc = subprocess.run(
        [
            sys.executable, "-m", "raft_ncup_tpu.analysis",
            str(tmp_path), "--format", "json",
        ],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    payload = _json.loads(proc.stdout)
    assert set(payload) == {
        "files_checked", "findings", "parse_errors",
        "stale_allowlist_entries", "exit_code",
    }
    assert payload["exit_code"] == 1 and proc.returncode == 1
    assert payload["parse_errors"] == []
    assert payload["files_checked"] >= 1
    [finding] = payload["findings"]
    assert set(finding) == {
        "rule", "path", "line", "col", "qualname", "message", "suppressed",
    }
    assert finding["rule"] == "JGL011"
    assert finding["suppressed"] is False
    assert isinstance(finding["line"], int)


def test_cli_json_output_clean_tree_exits_zero(tmp_path):
    import json as _json

    good = tmp_path / "mod.py"
    good.write_text("x = 1\n")
    proc = subprocess.run(
        [
            sys.executable, "-m", "raft_ncup_tpu.analysis",
            str(tmp_path), "--format", "json",
        ],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    payload = _json.loads(proc.stdout)
    assert proc.returncode == 0
    assert payload["exit_code"] == 0
    assert payload["findings"] == []


# ------------------------------------------------------- knob catalog


def test_perf_md_names_every_registered_knob():
    """docs/PERF.md carries the generated knob catalog: every registered
    knob name appears there (regenerate with
    `python -m raft_ncup_tpu.utils.knobs`)."""
    from raft_ncup_tpu.utils import knobs

    with open(os.path.join(REPO, "docs", "PERF.md"), encoding="utf-8") as fh:
        text = fh.read()
    missing = [k.name for k in knobs.KNOBS if f"`{k.name}`" not in text]
    assert not missing, (
        f"knobs missing from docs/PERF.md (regenerate the catalog with "
        f"`python -m raft_ncup_tpu.utils.knobs`): {missing}"
    )


def test_catalog_markdown_covers_registry():
    from raft_ncup_tpu.utils import knobs

    table = knobs.catalog_markdown()
    for knob in knobs.KNOBS:
        assert f"`{knob.name}`" in table


def test_every_knob_name_in_the_documents_is_registered():
    """The other direction: a full `RAFT_NCUP_*` name in README.md or
    docs/*.md is a declared knob (the bare prefix `RAFT_NCUP_` is not a
    name), so a document cannot advertise a switch the code never reads."""
    import glob
    import re

    from raft_ncup_tpu.utils import knobs

    declared = {k.name for k in knobs.KNOBS}
    paths = [os.path.join(REPO, "README.md")] + sorted(
        glob.glob(os.path.join(REPO, "docs", "*.md"))
    )
    unknown = {}
    for path in paths:
        with open(path, encoding="utf-8") as fh:
            names = set(re.findall(r"RAFT_NCUP_[A-Z0-9_]*[A-Z0-9]", fh.read()))
        if names - declared:
            unknown[os.path.relpath(path, REPO)] = sorted(names - declared)
    assert not unknown, f"undeclared knob names in the documents: {unknown}"
    assert len(declared) == 13


def test_every_path_of_the_readme_module_tree_exists():
    """README.md's "Layout" block names modules; each is in the tree. An
    entry is the head of a line before its description: under
    `raft_ncup_tpu/` when indented, at the root of the repo otherwise."""
    import re

    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as fh:
        text = fh.read()
    block = re.search(r"```\n(raft_ncup_tpu/\n.*?)```", text, re.S).group(1)
    missing, seen = [], 0
    for line in block.splitlines()[1:]:
        indent = len(line) - len(line.lstrip(" "))
        if indent not in (0, 2):
            continue  # a description's continuation line
        head = re.split(r"\s{2,}", line.strip(), maxsplit=1)[0]
        base = os.path.join(REPO, "raft_ncup_tpu") if indent else REPO
        for entry in re.split(r"[,\s]+", head):
            seen += 1
            if not os.path.exists(os.path.join(base, entry)):
                missing.append(entry)
    assert seen >= 25 and not missing, missing


# ------------------------------------------------------------ self-check


_TIMED_LINT = """
import sys, time
from raft_ncup_tpu.analysis.lint import DEFAULT_ALLOWLIST, run_lint
t0 = time.process_time()
run_lint(sys.argv[1:], allowlist_path=DEFAULT_ALLOWLIST)
print(time.process_time() - t0)
"""


def test_whole_program_pass_stays_fast():
    """The project pass (one extra AST walk + three cross-module rules)
    must not turn lint.sh into a coffee break: the full tree-wide run,
    all rules, stays under 5 CPU-seconds. Budgeted on process time, not
    wall — the pass is single-threaded work, and wall time on a loaded
    CI host measures the host's OTHER tenants, not a lint regression.
    Timed in a process of its own, as lint.sh pays it, and best of three:
    the pass is 2.3 CPU-seconds alone, up to 3.9 in a fresh process
    beside six busy ones, and read 5.9 inside an xdist worker ten minutes
    into the suite (PR 28: whatever that worker's earlier tests left
    behind is charged to it too). A regression shows in every try where
    a noisy neighbour does not."""
    paths = [
        os.path.join(REPO, p)
        for p in (
            "raft_ncup_tpu", "train.py", "evaluate.py", "demo.py",
            "serve.py", "scripts",
        )
    ]
    best = float("inf")
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, "-c", _TIMED_LINT, *paths],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        best = min(best, float(proc.stdout.split()[-1]))
        if best < 5.0:
            break
    assert best < 5.0


def test_shipped_tree_lints_clean_via_module_cli():
    """The acceptance contract: `python -m raft_ncup_tpu.analysis
    raft_ncup_tpu/` exits 0 on the shipped tree (allowlisted exceptions
    only). Run exactly as documented, from the repo root."""
    proc = subprocess.run(
        [sys.executable, "-m", "raft_ncup_tpu.analysis", "raft_ncup_tpu/"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, (
        f"graftlint found regressions:\n{proc.stdout}\n{proc.stderr}"
    )


def test_drivers_and_scripts_lint_clean():
    """lint.sh's wider scope (drivers, scripts) stays clean too —
    in-process, so the tier-1 gate catches driver regressions without a
    subprocess."""
    from raft_ncup_tpu.analysis.lint import DEFAULT_ALLOWLIST

    paths = [
        os.path.join(REPO, p)
        for p in (
            "raft_ncup_tpu", "train.py", "evaluate.py", "demo.py",
            "serve.py", "scripts",
        )
    ]
    result = run_lint(paths, allowlist_path=DEFAULT_ALLOWLIST)
    assert result.findings == [], [f.render() for f in result.findings]
    assert result.parse_errors == []
    assert result.stale_entries == [], [
        e.render() for e in result.stale_entries
    ]
