"""Iteration-pipelined inference over the ``pipe`` mesh axis
(docs/SHARDING.md "Pipeline axis"; ROADMAP item 2).

RAFT's GRU tower is a chain of N IDENTICAL refinement iterations
(PAPERS.md: arXiv:2003.12039) — exactly the structure pipeline-parallel
frameworks exploit (PAPERS.md: PPLL, arXiv:2411.12780). This module
splits the N iterations into S contiguous SEGMENTS placed on S device
groups (the ``pipe`` axis of ``parallel/mesh.make_mesh``) and streams
micro-batches through them so every group stays busy: while stage 1
refines request B's iterations 1..N/S, stage 2 refines request A's
iterations N/S+1..2N/S. At fixed per-request latency, steady-state
throughput approaches S× without growing the batch — and segment
boundaries are the natural early-exit points ROADMAP item 5 needs.

**The tick.** Pipeline state is the models' segment carry
(models/raft.py ``encode``: net, coords1, inp, fmap1, fmap2)
stacked along a leading STAGE axis of size S, sharded ``P("pipe")`` so
stage s's micro-batch lives on device group s. One tick of the
schedule is ONE compiled SPMD program:

1. **inject** — the freshly encoded micro-batch overwrites stage 0's
   slot (a sharded ``.at[0].set``);
2. **refine** — ``shard_map`` over ``pipe``: every stage advances its
   resident carry by N/S iterations (the same ``lax.scan`` step body
   as the monolithic ``apply``, via ``RAFT.refine_segment``);
3. **extract** — stage S-1's refined carry is the finished micro-batch;
   ``RAFT.finalize`` upsamples it to ``(flow_lr, flow_up)`` inside the
   same program;
4. **shift** — ``jax.lax.ppermute`` hands every refined carry to the
   next stage (``collective-permute`` in the compiled HLO — the
   pipeline's handoff traffic, attributable via
   ``parallel.mesh.collective_stats``'s per-op breakout).

The state operand is DONATED, so the carry buffers are reused in place
tick over tick. A micro-batch injected at tick t completes at tick
t+S-1; M micro-batches take M+S-1 ticks (S-1 of them flush ticks whose
stage-0 slot refines zeros that are never read). Warm-up and flush
outputs are discarded by the host driver, not computed around —
schedule uniformity is what keeps the steady state at exactly one
compiled program, zero recompiles.

**CPU emulation caveat** (tests/conftest.py's 8 virtual devices): the
virtual "device groups" share one host, so the S× throughput claim is
NOT measurable here — what IS pinnable is everything load-bearing:
output parity with the monolithic scan, carry-handoff correctness at
every seam, donation, guard-clean steady state, and the
collective-permute fingerprint. The throughput claim is not measured:
no cell of the benchmark runs the pipe axis (ROADMAP D6).

**v1 scope**: the pipe axis composes with ``data``/``spatial`` sizes
of 1 only. Running spatial sharding INSIDE a pipeline stage needs the
halo-exchange-aware corr path scoped to the stage's subgroup —
staged behind the same chip window (docs/SHARDING.md).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# The version-resolved shard_map the model's sharded corr path already
# uses (keyword-compatible across jax's experimental->top-level move).
from raft_ncup_tpu.models.raft import _shard_map

# Images enter every forward executable as f32 regardless of precision
# policy (precision.PrecisionPolicy: inputs stay f32, casts happen
# inside the model) — the carry eval_shape must trace with the same
# pinned input dtype or the stacked state would disagree with what
# encode actually produces.
IMAGE_DTYPE = jnp.float32


def split_iters(iters: int, segments: int) -> int:
    """Iteration count -> per-segment length. Segments are equal-length
    contiguous blocks, so ``segments`` must divide ``iters`` — a ragged
    last segment would need its own executable and break the
    one-program steady state."""
    iters, segments = int(iters), int(segments)
    if segments < 1:
        raise ValueError(f"segments must be >= 1, got {segments}")
    if iters < 1 or iters % segments:
        raise ValueError(
            f"iters={iters} does not split into {segments} equal scan "
            f"segments; pipelined budgets must be multiples of "
            f"{segments} (see serving/budget.py segment quantization)"
        )
    return iters // segments


def validate_segment_levels(
    levels: Sequence[int], segments: int
) -> None:
    """Budget quantization rule for a pipelined deployment: every
    iteration level must land on a SEGMENT BOUNDARY — i.e. be a
    multiple of the segment length ``levels[0] / segments`` — because a
    reduced budget runs fewer segments of the same compiled tick, and
    a budget strictly inside a segment would need a fresh executable
    per level (the recompile storm the fixed level set exists to
    prevent). E.g. ``(24, 16, 8)`` with S=2 (segment length 12) is
    INVALID (16 and 8 sit mid-segment); ``(24, 12)`` is valid."""
    segments = int(segments)
    if segments < 1:
        raise ValueError(f"segments must be >= 1, got {segments}")
    if segments == 1:
        return  # monolithic: every level is its own boundary
    levels = tuple(int(x) for x in levels)
    if not levels:
        raise ValueError("empty iteration level set")
    if levels[0] % segments:
        raise ValueError(
            f"top iteration level {levels[0]} does not split into "
            f"{segments} equal segments"
        )
    seg_len = levels[0] // segments
    bad = [x for x in levels if x % seg_len]
    if bad:
        raise ValueError(
            f"iteration levels {bad} do not quantize to the segment "
            f"boundary (multiples of {levels[0]}/{segments} = {seg_len} "
            f"iterations) required by pipe segments={segments}; with a "
            "pipelined mesh a budget level must run a whole number of "
            f"scan segments — e.g. {tuple(seg_len * k for k in range(segments, 0, -1))}"
        )


class PipelinedForward:
    """Micro-batch streaming driver for the iteration pipeline.

    Compiled programs (the per-micro-batch ``pipe_encode`` and the
    steady-state ``pipe_tick``) live in a :class:`ShapeCachedForward`
    — same LRU bound, compiles/hits/evictions accounting, telemetry,
    and cost-ledger instrumentation as every other executable, keyed
    under the pipe mesh's fingerprint plus the segment count so
    pipelined and monolithic executables can never collide.

    ``segments == 1`` is EXACTLY the monolithic path: ``forward_many``
    delegates straight to ``ShapeCachedForward.forward_device`` (one
    ``apply`` scan, no pipeline machinery, no pipe mesh) — the default
    config pays nothing for this module's existence.
    """

    def __init__(
        self, model, variables: dict, mesh=None,
        segments: Optional[int] = None, cache_size: int = 8,
        policy=None, telemetry=None, cost_ledger=None,
    ):
        from raft_ncup_tpu.inference.pipeline import ShapeCachedForward
        from raft_ncup_tpu.parallel.mesh import make_mesh

        if mesh is None and segments is not None and int(segments) > 1:
            mesh = make_mesh(
                data=1, spatial=1, pipe=int(segments),
                devices=jax.devices()[: int(segments)],
            )
        s = int(mesh.shape.get("pipe", 1)) if mesh is not None else 1
        if segments is not None and int(segments) != s:
            raise ValueError(
                f"segments={segments} disagrees with mesh pipe axis {s}"
            )
        if s > 1:
            extra = {
                k: v for k, v in mesh.shape.items()
                if k != "pipe" and int(v) > 1
            }
            if extra:
                raise ValueError(
                    f"pipe axis composes with data/spatial sizes of 1 "
                    f"only (got {dict(mesh.shape)}); spatially-sharded "
                    "pipeline stages are staged for the chip window "
                    "(docs/SHARDING.md)"
                )
        self.segments = s
        self.mesh = mesh if s > 1 else None
        self.model = model
        self.variables = variables
        self.cache = ShapeCachedForward(
            model, variables, mesh=self.mesh, cache_size=cache_size,
            policy=policy, telemetry=telemetry, cost_ledger=cost_ledger,
        )
        # Warmed tick callables by (shape, iters, segments, policy) —
        # the inspection surface tick_text() reads compiled HLO from
        # without paying a second compile.
        self._tick_handles: dict = {}

    @property
    def is_pipelined(self) -> bool:
        return self.segments > 1

    # ------------------------------------------------------------ programs

    def _carry_struct(
        self, image_shape: tuple, model, early_exit: bool = False,
    ) -> dict:
        img = jax.ShapeDtypeStruct(tuple(image_shape), IMAGE_DTYPE)
        return jax.eval_shape(
            lambda v, a, b: model.encode(v, a, b, early_exit=early_exit),
            self.variables, img, img,
        )

    def _build_encode(self, model, early_exit: bool = False):
        repl = NamedSharding(self.mesh, P())

        def enc(v, i1, i2):
            return model.encode(v, i1, i2, early_exit=early_exit)

        return jax.jit(enc, in_shardings=(repl, repl, repl),
                       out_shardings=repl)

    def _build_tick(self, model, seg_len: int, early_exit_tol=None):
        mesh = self.mesh
        s = self.segments
        perm = [(i, i + 1) for i in range(s - 1)]

        def seg_local(v, block):
            # One pipeline stage: its (1, B, ...) slot of the stacked
            # state, squeezed to the plain segment carry, advanced by
            # seg_len iterations of the SAME step body as apply().
            local = jax.tree.map(lambda x: x[0], block)
            out = model.refine_segment(
                v, local, seg_len, early_exit_tol=early_exit_tol
            )
            out = jax.tree.map(lambda x: x[None], out)
            # Carry handoff: refined stage s -> stage s+1. Stage 0's
            # incoming slot is zero-filled by ppermute (no source) and
            # immediately overwritten by the next tick's inject.
            shifted = jax.tree.map(
                lambda x: jax.lax.ppermute(x, "pipe", perm), out
            )
            return out, shifted

        def tick(v, state, fresh):
            state = jax.tree.map(
                lambda st, f: st.at[0].set(f), state, fresh
            )
            refined, shifted = _shard_map(
                seg_local, mesh=mesh,
                in_specs=(P(), P("pipe")),
                out_specs=(P("pipe"), P("pipe")),
            )(v, state)
            done = jax.tree.map(lambda x: x[s - 1], refined)
            flow_lr, flow_up = model.finalize(v, done)
            if early_exit_tol is not None:
                # The finished micro-batch's per-sample executed-iters
                # counter (quantized to segment boundaries inside
                # refine_segment) leaves with its flow — one more tiny
                # replicated output, no extra sync.
                return shifted, flow_lr, flow_up, done["exec_iters"]
            return shifted, flow_lr, flow_up

        repl = NamedSharding(self.mesh, P())
        staged = NamedSharding(self.mesh, P("pipe"))
        n_out = 3 if early_exit_tol is None else 4
        # Donating the state keeps the pipeline's carry buffers reused
        # in place tick over tick — steady-state memory is one stacked
        # carry, not one per in-flight tick.
        return jax.jit(
            tick,
            in_shardings=(repl, staged, repl),
            out_shardings=(staged,) + (repl,) * (n_out - 1),
            donate_argnums=(1,),
        )

    def _programs(
        self, image_shape: tuple, iters: int, policy=None,
        early_exit_tol=None,
    ):
        """(encode, tick, model, pol) — compiled-on-first-call via the
        cache, keyed by (shape, iters, segments, policy). Early-exit
        programs append a ``("earlyexit", tol)`` key element (exactly
        like ``forward_device``): detection-off deployments keep their
        existing keys and executables untouched."""
        model, pol = self.cache.model_for(policy)
        seg_len = split_iters(iters, self.segments)
        shape = tuple(image_shape)
        fp = pol.fingerprint()
        ee_key = ()
        if early_exit_tol is not None:
            early_exit_tol = float(early_exit_tol)
            ee_key = (("earlyexit", early_exit_tol),)
        enc = self.cache.custom(
            ("pipe_encode", shape, fp) + ee_key,
            lambda: self._build_encode(
                model, early_exit=early_exit_tol is not None
            ),
        )
        tick = self.cache.custom(
            ("pipe_tick", shape, int(iters), self.segments, fp) + ee_key,
            lambda: self._build_tick(
                model, seg_len, early_exit_tol=early_exit_tol
            ),
        )
        self._tick_handles[
            (shape, int(iters), self.segments, fp) + ee_key
        ] = tick
        return enc, tick, model, pol

    def _zero_state(self, carry_sds: dict):
        staged = NamedSharding(self.mesh, P("pipe"))
        s = self.segments
        return jax.tree.map(
            lambda sd: jax.device_put(
                jnp.zeros((s,) + tuple(sd.shape), sd.dtype), staged
            ),
            carry_sds,
        )

    def _zero_fresh(self, carry_sds: dict):
        repl = NamedSharding(self.mesh, P())
        return jax.tree.map(
            lambda sd: jax.device_put(
                jnp.zeros(tuple(sd.shape), sd.dtype), repl
            ),
            carry_sds,
        )

    # ------------------------------------------------------------- driving

    def forward_many(
        self, pairs: Sequence[tuple], iters: int, policy=None,
        early_exit_tol: Optional[float] = None,
    ) -> list:
        """Stream ``pairs`` (same-shape ``(image1, image2)`` micro-
        batches) through the pipeline; returns the per-micro-batch
        ``(flow_lr, flow_up)`` DEVICE arrays in submission order (the
        caller owns the pull, as with ``forward_device``).

        ``len(pairs)`` micro-batches take ``len(pairs) + S - 1`` ticks
        (S-1 flush ticks at the tail). The steady state is guard-clean:
        every tick after the first reuses the same two executables and
        performs no host transfer.

        ``early_exit_tol`` (docs/PERF.md "Early exit"): each result
        becomes the 3-tuple ``(flow_lr, flow_up, exec_iters)``. Under
        the pipe axis exits QUANTIZE to segment boundaries — the tick
        schedule is fixed, so a converged lane rides frozen (bitwise,
        per-iteration ``jnp.where`` inside ``refine_segment``) to the
        next seam and ``exec_iters`` bills whole segments:
        ``exec_pipe == ceil(exec_mono / seg_len) * seg_len``.
        """
        if self.segments == 1:
            return [
                self.cache.forward_device(
                    i1, i2, iters, policy=policy,
                    early_exit_tol=early_exit_tol,
                )
                for i1, i2 in pairs
            ]
        split_iters(iters, self.segments)  # validate before compiling
        pairs = list(pairs)
        if not pairs:
            return []
        shape = tuple(jnp.shape(pairs[0][0]))
        enc, tick, model, _pol = self._programs(
            shape, iters, policy, early_exit_tol=early_exit_tol
        )
        carry_sds = self._carry_struct(
            shape, model, early_exit=early_exit_tol is not None
        )
        state = self._zero_state(carry_sds)
        flush = self._zero_fresh(carry_sds)
        s = self.segments
        outs = []
        for t in range(len(pairs) + s - 1):
            if t < len(pairs):
                i1, i2 = pairs[t]
                fresh = enc(
                    self.variables, jnp.asarray(i1), jnp.asarray(i2)
                )
            else:
                fresh = flush
            if early_exit_tol is not None:
                state, flow_lr, flow_up, exec_iters = tick(
                    self.variables, state, fresh
                )
                if t >= s - 1:
                    outs.append((flow_lr, flow_up, exec_iters))
            else:
                state, flow_lr, flow_up = tick(
                    self.variables, state, fresh
                )
                if t >= s - 1:
                    outs.append((flow_lr, flow_up))
        return outs

    # ---------------------------------------------------------- inspection

    def tick_text(
        self, image_shape: tuple, iters: int, policy=None,
    ) -> Optional[str]:
        """Optimized HLO text of the WARMED tick executable — the
        program that actually served ``forward_many`` — read from the
        cache's instrumentation handle at zero compile cost. ``None``
        before the first call for this (shape, iters, policy), or when
        the cost ledger (whose AOT warm-up produces the handle) is
        disabled; ``tick_hlo`` is the always-works fallback at one
        fresh compile."""
        if self.segments == 1:
            return None
        _model, pol = self.cache.model_for(policy)
        key = (
            tuple(image_shape), int(iters), self.segments,
            pol.fingerprint(),
        )
        fn = self._tick_handles.get(key)
        box = getattr(fn, "_compiled_box", None)
        compiled = box.get("c") if box else None
        if compiled is None or not hasattr(compiled, "as_text"):
            return None
        try:
            return compiled.as_text()
        except Exception:  # pragma: no cover - backend-specific
            return None

    def tick_hlo(self, image_shape: tuple, iters: int, policy=None) -> str:
        """Optimized HLO text of the steady-state tick program, compiled
        fresh for inspection (``collective_stats`` fingerprinting in
        tests and the bench row) — the served executable in the cache is
        untouched."""
        if self.segments == 1:
            raise ValueError("segments=1 has no tick program")
        model, _pol = self.cache.model_for(policy)
        seg_len = split_iters(iters, self.segments)
        carry_sds = self._carry_struct(tuple(image_shape), model)
        state_sds = jax.tree.map(
            lambda sd: jax.ShapeDtypeStruct(
                (self.segments,) + tuple(sd.shape), sd.dtype
            ),
            carry_sds,
        )
        jt = self._build_tick(model, seg_len)
        return jt.lower(self.variables, state_sds, carry_sds).compile().as_text()
