"""Validation and leaderboard-submission drivers.

Mirrors the reference eval surface (reference: evaluate.py:22-182):
``validate_chairs`` (EPE @ 24 iters), ``validate_sintel`` (clean+final
EPE and 1/3/5px @ 32 iters), ``validate_kitti`` (EPE + F1 @ 24 iters),
and the Sintel/KITTI submission writers (warm-start supported for
Sintel).

Built on the async inference subsystem (``raft_ncup_tpu/inference/``;
docs/PERF.md "Eval pipeline"):

- Validators stream batches through :class:`EvalPipeline` (decode →
  host stage/pad → device transfer, all off the dispatch thread) and
  fold EPE/F1 **on device** inside the jitted forward
  (``inference/metrics.py`` via ``RAFT.apply(metric_head=...)``). The
  host pulls a handful of accumulator scalars ONCE per dataset window —
  never a flow field — so the steady-state loop runs clean under
  ``analysis/guards.forbid_host_transfers``.
- Submissions still need full-field pulls; they go through
  :class:`AsyncDrain`, which performs the sanctioned ``jax.device_get``
  on a worker thread behind dispatch.
- Compiled test-mode executables are cached per shape in a bounded LRU
  (:class:`ShapeCachedForward`, knob ``DataConfig.eval_cache_size``). A
  validation pass groups its samples by native size across the stream
  (``inference/pipeline.uniform_batches``), and a pass with a ``valid``
  mask (KITTI) fills each size's remainder to a full batch with rows the
  metric head counts as no frame: the executables of a KITTI pass are one
  a native size (four for KITTI-2015), whatever the order of the frames,
  and nothing is built after the first pass. Pad bucketing
  (``DataConfig.eval_pad_bucket``) is separate and off by default: every
  frame is padded to its own multiple of 8, as upstream pads it.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np

from raft_ncup_tpu.config import DataConfig
from raft_ncup_tpu.data import datasets as ds_mod
from raft_ncup_tpu.inference import metrics as metrics_mod
from raft_ncup_tpu.inference.pipeline import (
    AsyncDrain,
    DispatchThrottle,
    EvalPipeline,
    SamplePrefetcher,
    ShapeCachedForward,
)
from raft_ncup_tpu.io import write_flo, write_flow_kitti
from raft_ncup_tpu.models.raft import RAFT
from raft_ncup_tpu.observability import get_telemetry, new_span_id
from raft_ncup_tpu.ops import InputPadder
from raft_ncup_tpu.ops.warmstart import forward_interpolate_batch
from raft_ncup_tpu.parallel.multihost import (
    allreduce_sum_across_hosts,
    is_main_process,
    is_multihost,
)
from raft_ncup_tpu.viz import flow_to_image


def _pad_divisor(mesh) -> int:
    """Images must pad so the 1/8-res feature height divides the mesh's
    spatial axis, else the model's corr lookup cannot take the shard_map
    path (models/raft.py) and GSPMD partitions it pathologically."""
    if mesh is None:
        return 8
    return 8 * int(mesh.shape.get("spatial", 1))


class _HostShard:
    """Round-robin view of a dataset restricted to this process's frames
    (indices ``process_index::process_count``), so a multi-host job
    validates each frame exactly once instead of every host duplicating
    the full pass (VERDICT r4 weak #4). ``n_global`` bounds indexing to
    the cross-host AGREED length (hosts with divergent disks must not
    index frames others lack)."""

    def __init__(self, dataset, n_global: int):
        self._ds = dataset
        self._n = n_global
        self._pi = jax.process_index()
        self._pc = jax.process_count()

    def __len__(self) -> int:
        return (self._n - self._pi + self._pc - 1) // self._pc

    def sample(self, index: int, *a, **kw):
        return self._ds.sample(self._pi + index * self._pc, *a, **kw)


def _shard_for_validation(dataset, mesh):
    """Decide the multi-host validation plan for one dataset.

    Returns ``(dataset_view, n_agreed, do_reduce)``:

    - Host-local forward (``mesh is None``): frames are host-sharded and
      the metric sums all-reduce afterwards — each frame computed once.
    - Global SPMD mesh: every process MUST execute every jitted forward
      in lockstep (the program contains cross-host collectives), so the
      dataset is left whole, all hosts compute identical global metrics,
      and reduction is the identity. Sharding here would desynchronize
      the collectives and hang the pod.

    ``n_agreed`` is the cross-host minimum length, so a host whose disk
    is missing the dataset makes EVERY host skip consistently — a
    host-local skip with a global collective pending deadlocks the rest.
    """
    n = len(dataset)
    if jax.process_count() == 1:
        return dataset, n, False
    from jax.experimental import multihost_utils

    lens = np.asarray(multihost_utils.process_allgather(np.asarray([n])))
    n = int(lens.min())
    if mesh is not None:
        if n != len(dataset):
            return _Truncated(dataset, n), n, False
        return dataset, n, False
    return _HostShard(dataset, n), n, True


class _Truncated:
    """Identity view capped at the cross-host agreed length (lockstep
    SPMD iteration requires every host to run the same batch count)."""

    def __init__(self, dataset, n: int):
        self._ds = dataset
        self._n = n

    def __len__(self) -> int:
        return self._n

    def sample(self, index: int, *a, **kw):
        return self._ds.sample(index, *a, **kw)


def _print_main(msg: str) -> None:
    """Validator console lines only from one process on a pod."""
    if is_main_process():
        print(msg)


def _pad_host(pad_spec, *arrays: np.ndarray) -> list[np.ndarray]:
    """Apply an InputPadder spec with host-side np.pad (replicate edges).

    Staging runs on the EvalPipeline's worker thread; padding there with
    ``jnp.pad`` (InputPadder.pad) would put device work — and a device
    array round-trip — on the staging thread. The spec is identical, the
    backend is not.
    """
    (t, b), (l, r) = pad_spec
    spec = ((0, 0), (t, b), (l, r), (0, 0))
    return [np.pad(x, spec, mode="edge") for x in arrays]


def _stack(arrays: list, dtype) -> np.ndarray:
    """``np.stack`` straight into ``dtype``: one write of the batch, where
    ``np.stack(...).astype(...)`` writes it twice."""
    return np.stack(arrays, dtype=dtype, casting="unsafe")


def _stage_batch(
    group: list, *, pad_mode: Optional[str] = None, divisor: int = 8,
    bucket: int = 0, with_valid: bool = False, band_fn=None,
) -> tuple[dict, Optional[tuple]]:
    """Validation's one staging convention: ``(arrays, pad_spec)`` of a
    group of samples, at the width the dataset hands them.

    Frames that arrive uint8 (the datasets' contract, data/datasets.py;
    the benchmark's pool) are stacked, padded and later copied AS uint8:
    edge replication of uint8 is the same pixels, and the widening to
    float32 is the first thing ``ShapeCachedForward.metrics``'s program
    does, exact, so the encoders read the values they always read at a
    quarter of the host's writes and of the copy. A batch with a frame of
    any other dtype is staged float32, as it always was: the form follows
    what the samples hold, nothing selects it. ``flow`` / ``valid`` /
    ``band`` are float32 at native shape, written once. ``pad_mode`` None
    skips padding (``pad_spec`` None)."""
    frames = [s[k] for s in group for k in ("image1", "image2")]
    narrow = all(np.asarray(f).dtype == np.uint8 for f in frames)
    width = np.uint8 if narrow else np.float32
    img1 = _stack([s["image1"] for s in group], width)
    img2 = _stack([s["image2"] for s in group], width)
    arrays = {"flow": _stack([s["flow"] for s in group], np.float32)}
    if with_valid:
        arrays["valid"] = _stack([s["valid"] for s in group], np.float32)
    if band_fn is not None:
        arrays["band"] = _stack(
            [band_fn(s["flow"]) for s in group], np.float32
        )
    pad = None
    if pad_mode is not None:
        pad = InputPadder(
            img1.shape, mode=pad_mode, divisor=divisor, bucket=bucket
        ).pad_spec
        img1, img2 = _pad_host(pad, img1, img2)
    arrays["image1"], arrays["image2"] = img1, img2
    return arrays, pad


def _run_metric_pass(
    fwd: ShapeCachedForward,
    dataset,
    *,
    kind: str,
    iters: int,
    batch_size: int,
    mesh=None,
    pad_mode: Optional[str] = None,
    bucket: int = 0,
    with_valid: bool = False,
    band_fn=None,
    num_workers: int = 4,
    depth: int = 2,
    telemetry=None,
) -> np.ndarray:
    """One validation pass: stream ``dataset`` through the
    double-buffered :class:`EvalPipeline`, folding every batch into an
    on-device ``kind`` accumulator inside the jitted forward, and pull
    the handful of sums to host with ONE sanctioned ``jax.device_get``
    at the window end. No flow field crosses to host.

    ``pad_mode`` None skips padding (chairs/synthetic shapes are already
    stride-aligned); otherwise images pad host-side on the staging
    thread and the static pad spec rides the batch meta so the jitted
    program crops predictions in-graph (metrics.unpad_in_graph). Frames
    are staged and copied at the dataset's own width (uint8 stays uint8,
    :func:`_stage_batch`); the metrics program widens them.
    ``band_fn`` (epe_band only) computes the host-side boundary mask
    during staging. Returns the host accumulator (float32 sums, ready
    for ``allreduce_sum_across_hosts`` + ``metrics.finalize``).

    Spans, on ``telemetry`` (None: the process hub), all with this pass's
    ``pass_id`` and the batch's index: the pipeline's ``input_wait`` /
    ``input_stage`` / ``input_h2d`` (data/device_prefetch.py), then per
    batch ``eval_dispatch`` (the jit dispatch; it carries the batch's
    native ``size``) and ``eval_throttle_wait``
    (the bounded wait for an earlier batch), and once ``eval_pull`` (the
    pass's one pull, which waits for the device to finish). Where
    ``eval_dispatch`` closes three counters grow: ``eval_rows_total`` by
    the batch's rows, ``eval_fill_rows_total`` by those of them that are
    fill rows (``with_valid`` passes fill each size's remainder to a full
    batch, ``uniform_batches``) and ``eval_pairs_total`` by the real pairs
    alone; ``eval_input_bytes_total`` grows by the staged
    batch's host bytes (frames, ground truth, masks: what ``input_h2d``
    then copies) where it is staged; bytes a pair says which width the
    frames went at. After the pull the pass publishes which
    precision its executable ran at (:func:`_publish_precision`) and what
    it built (:func:`_publish_programs`).
    """
    tel = telemetry if telemetry is not None else get_telemetry()
    pass_id = new_span_id()
    divisor = _pad_divisor(mesh)

    def stage(group: list) -> tuple:
        arrays, pad = _stage_batch(
            group, pad_mode=pad_mode, divisor=divisor, bucket=bucket,
            with_valid=with_valid, band_fn=band_fn,
        )
        tel.inc(
            "eval_input_bytes_total", sum(a.nbytes for a in arrays.values())
        )
        h, w = group[0]["image1"].shape[:2]
        return arrays, {
            "pad": pad, "rows": len(group), "size": f"{h}x{w}",
            "fill": sum(1 for s in group if s.get("fill")),
        }

    shardings = None
    if mesh is not None and not is_multihost():
        # Transfer each batch straight into the compiled program's input
        # layout (images sharded over (batch, height), metric operands
        # replicated — ShapeCachedForward._jit) so the worker thread owns
        # the distribution and jit dispatch does no re-layout. Multihost
        # global-mesh eval stages the FULL batch on every host
        # (_shard_for_validation's lockstep plan), which is not the
        # per-host-local-shard contract device_put_batch's global_batch
        # path expects — there, placement stays with jit dispatch.
        from jax.sharding import NamedSharding, PartitionSpec as P

        img = NamedSharding(mesh, P("data", "spatial"))
        repl = NamedSharding(mesh, P())
        shardings = {
            "image1": img, "image2": img,
            "flow": repl, "valid": repl, "band": repl,
        }

    acc = metrics_mod.init_acc(kind)
    throttle = DispatchThrottle()  # backend-tuned in-flight bound
    built_before = dict(getattr(fwd, "stats", {}))
    with EvalPipeline(
        dataset,
        stage,
        batch_size=batch_size,
        fill_valid=with_valid,
        depth=depth,
        num_workers=num_workers,
        mesh=mesh,
        shardings=shardings,
        telemetry=tel,
        span_attrs={"pass_id": pass_id},
    ) as pipe:
        for index, (batch, meta) in enumerate(pipe):
            attrs = {"pass_id": pass_id, "batch": index}
            with tel.span("eval_dispatch", size=meta["size"], **attrs):
                acc = fwd.metrics(
                    batch, iters=iters, acc=acc, kind=kind, pad=meta["pad"]
                )
            tel.inc("eval_pairs_total", meta["rows"] - meta["fill"])
            tel.inc("eval_rows_total", meta["rows"])
            tel.inc("eval_fill_rows_total", meta["fill"])
            with tel.span("eval_throttle_wait", **attrs):
                throttle.push(acc)
    # The window's single sanctioned pull: a few float32 sums, not fields.
    with tel.span("eval_pull", pass_id=pass_id):
        host_acc = jax.device_get(acc)
    _publish_precision(tel, fwd)
    _publish_programs(tel, fwd, built_before, pass_id)
    return np.asarray(host_acc, np.float64)


def _publish_programs(tel, fwd, before: dict, pass_id) -> None:
    """What the pass cost the executable cache: the gauge
    ``eval_programs_resident`` (executables the cache holds now: compiles
    less evictions) and the event ``eval_pass_programs`` with the pass's
    own ``compiles`` and ``evictions`` (``ShapeCachedForward.stats`` now
    less ``before``, read when the pass began). A pass after the first
    reads 0 and 0; anything else is a shape the earlier passes did not
    bring, or a cache smaller than the pass's set of sizes. Nothing for a
    stand-in without ``stats``."""
    stats = getattr(fwd, "stats", None)
    if not stats:
        return
    tel.gauge_set("eval_programs_resident", stats["compiles"] - stats["evictions"])
    tel.event(
        "eval_pass_programs", pass_id=pass_id,
        compiles=stats["compiles"] - before.get("compiles", 0),
        evictions=stats["evictions"] - before.get("evictions", 0),
    )


def _publish_precision(tel, fwd) -> None:
    """The pass's executable by its banked product-site tally
    (``ShapeCachedForward.report()["precision"]``: preset, sites by operand
    width), as the gauges ``infer_product_sites_f32`` /
    ``infer_product_sites_bf16`` of the pass's hub: what ran pinned and what
    ran at one MXU pass, where an operator reads everything else
    (docs/OBSERVABILITY.md). Nothing for a stand-in without the report or
    with the cost ledger off."""
    report = getattr(fwd, "report", None)
    precision = report()["precision"] if report is not None else None
    if precision:
        tel.gauge_set("infer_product_sites_f32", precision["sites_f32"])
        tel.gauge_set("infer_product_sites_bf16", precision["sites_bf16"])


# The device-side warm-start splat: jit caches one tiny executable per
# low-res shape; the result stays on device and feeds the next frame's
# flow_init (submissions) or metric program (warm-start validation).
_device_splat = jax.jit(lambda f: forward_interpolate_batch(f))


def _run_warmstart_metric_pass(
    fwd: ShapeCachedForward,
    dataset,
    *,
    kind: str,
    iters: int,
    pad_mode: str = "sintel",
    num_workers: int = 4,
    sequence_of=None,
) -> np.ndarray:
    """Warm-start validation pass: frames stream IN ORDER (batch size 1
    — warm start is a serial per-sequence dependence), each frame's
    metric folds on device inside the jitted forward, and the next
    frame's ``flow_init`` is the device forward-splat of this frame's
    low-res flow. The chain ``flow_lr → splat → flow_init`` never
    touches the host; the window ends with ONE sanctioned
    ``jax.device_get`` of the accumulator sums.

    ``sequence_of(sample)`` names the sample's sequence (default: first
    element of ``extra_info``); a sequence change resets the warm chain
    to cold (zeros ``flow_init`` — bitwise identical to a cold start,
    and the SAME executable, so sequence boundaries cannot recompile).

    Single-host only: warm start needs sequence-adjacent frames, which
    is exactly what ``_HostShard``'s round-robin would destroy.
    """
    import jax.numpy as jnp

    if sequence_of is None:
        def sequence_of(s):
            info = s.get("extra_info")
            return info[0] if info else None

    acc = metrics_mod.init_acc(kind)
    throttle = DispatchThrottle()
    flow_prev = None
    seq_prev = object()  # never equal to a real sequence name
    with SamplePrefetcher(dataset, num_workers=num_workers) as samples:
        for s in samples:
            sequence = sequence_of(s)
            if sequence != seq_prev:
                flow_prev = None
            batch, pad = _stage_batch([s], pad_mode=pad_mode)
            if flow_prev is None:
                # Cold frames reuse the warm executable with a zero
                # init (coords + 0 is bitwise the cold start), so the
                # whole pass is ONE program per shape.
                _, h, w, _ = batch["image1"].shape
                flow_prev = jnp.zeros((1, h // 8, w // 8, 2), jnp.float32)
            acc, flow_lr = fwd.metrics(
                batch, iters=iters, acc=acc, kind=kind, pad=pad,
                flow_init=flow_prev,
            )
            flow_prev = _device_splat(flow_lr)
            throttle.push(acc)
            seq_prev = sequence
    return np.asarray(jax.device_get(acc), np.float64)


def validate_chairs(
    model: RAFT, variables: dict, data_cfg: Optional[DataConfig] = None,
    iters: int = 24, batch_size: int = 4, mesh=None,
    precision: Optional[str] = None,
) -> dict:
    """FlyingChairs validation-split EPE (reference: evaluate.py:90-108)."""
    cfg = data_cfg or DataConfig()
    dataset = ds_mod.FlyingChairs(
        None, split="validation", root=cfg.root_chairs,
        split_file=cfg.chairs_split_file,
    )
    dataset, n, do_reduce = _shard_for_validation(dataset, mesh)
    if n == 0:
        _print_main(f"validate_chairs: no data under {cfg.root_chairs}, skipping")
        return {}
    fwd = ShapeCachedForward(
        model, variables, mesh=mesh, cache_size=cfg.eval_cache_size,
        policy=precision,
    )
    acc = _run_metric_pass(
        fwd, dataset, kind="epe", iters=iters, batch_size=batch_size,
        mesh=mesh, num_workers=cfg.num_workers, depth=cfg.device_prefetch,
    )
    if do_reduce:
        acc = allreduce_sum_across_hosts(acc)
    epe = metrics_mod.finalize("epe", acc)["epe"]
    _print_main(f"Validation Chairs EPE: {epe:f}")
    return {"chairs": epe}


def validate_sintel(
    model: RAFT, variables: dict, data_cfg: Optional[DataConfig] = None,
    iters: int = 32, batch_size: int = 2, mesh=None,
    warm_start: bool = False, precision: Optional[str] = None,
) -> dict:
    """Sintel train-split clean+final EPE / 1px / 3px / 5px
    (reference: evaluate.py:111-143).

    ``warm_start=True`` evaluates the video scenario the reference's
    ``--warm_start`` submission uses: frames stream sequentially (batch
    size 1), each frame's ``flow_init`` is the device forward-splat of
    the previous frame's low-res flow, and sequence changes reset to
    cold. Single-host only (the warm chain needs sequence-adjacent
    frames; host-sharding would break it) and incompatible with a
    spatial mesh."""
    cfg = data_cfg or DataConfig()
    if warm_start and (mesh is not None or is_multihost()):
        raise ValueError(
            "warm-start validation is a serial per-sequence chain: "
            "single host, no mesh (see _run_warmstart_metric_pass)"
        )
    fwd = ShapeCachedForward(
        model, variables, mesh=mesh, cache_size=cfg.eval_cache_size,
        policy=precision,
    )
    results = {}
    prefix = "warm_" if warm_start else ""
    for dstype in ("clean", "final"):
        dataset = ds_mod.MpiSintel(
            None, split="training", root=cfg.root_sintel, dstype=dstype
        )
        if warm_start:
            if len(dataset) == 0:
                _print_main(
                    f"validate_sintel: no {dstype} data under "
                    f"{cfg.root_sintel}, skipping"
                )
                continue
            acc = _run_warmstart_metric_pass(
                fwd, dataset, kind="px", iters=iters,
                num_workers=cfg.num_workers,
            )
        else:
            dataset, n, do_reduce = _shard_for_validation(dataset, mesh)
            if n == 0:
                _print_main(
                    f"validate_sintel: no {dstype} data under "
                    f"{cfg.root_sintel}, skipping"
                )
                continue
            acc = _run_metric_pass(
                fwd, dataset, kind="px", iters=iters,
                batch_size=batch_size, mesh=mesh, pad_mode="sintel",
                num_workers=cfg.num_workers, depth=cfg.device_prefetch,
            )
            if do_reduce:
                acc = allreduce_sum_across_hosts(acc)
        m = metrics_mod.finalize("px", acc)
        _print_main(
            f"Validation ({prefix}{dstype}) EPE: {m['epe']:f}, "
            f"1px: {m['1px']:f}, 3px: {m['3px']:f}, 5px: {m['5px']:f}"
        )
        results[f"{prefix}{dstype}"] = m["epe"]
        results.update(
            {
                f"{prefix}{dstype}_1px": m["1px"],
                f"{prefix}{dstype}_3px": m["3px"],
                f"{prefix}{dstype}_5px": m["5px"],
            }
        )
    return results


def validate_sintel_warm(
    model: RAFT, variables: dict, data_cfg: Optional[DataConfig] = None,
    **kwargs,
) -> dict:
    """Sintel warm-start (video) validation — see :func:`validate_sintel`."""
    return validate_sintel(
        model, variables, data_cfg, warm_start=True, **kwargs
    )


def validate_kitti(
    model: RAFT, variables: dict, data_cfg: Optional[DataConfig] = None,
    iters: int = 24, batch_size: int = 2, mesh=None,
    precision: Optional[str] = None,
) -> dict:
    """KITTI-2015 train-split EPE + F1 (reference: evaluate.py:146-182).
    F1 = % of valid pixels with epe > 3 and epe/mag > 0.05.

    Frames group by native size across the whole stream
    (``uniform_batches``): KITTI-2015's 200 pairs come in four sizes in
    no useful order, every group is a full batch (a size's remainder is
    filled with rows whose ``valid`` is all zero, which the metric head
    counts as no frame), and the pass runs one executable a native size,
    built in the first pass and never again. Each frame is padded to its
    own multiple of 8 (``DataConfig.eval_pad_bucket`` stays 0 unless
    asked for). The reference streams singletons; per-frame metric
    semantics are unchanged and the sums commute: EPE averages per
    frame, F1 pools valid pixels."""
    cfg = data_cfg or DataConfig()
    dataset = ds_mod.KITTI(None, split="training", root=cfg.root_kitti)
    dataset, n, do_reduce = _shard_for_validation(dataset, mesh)
    if n == 0:
        _print_main(f"validate_kitti: no data under {cfg.root_kitti}, skipping")
        return {}
    fwd = ShapeCachedForward(
        model, variables, mesh=mesh, cache_size=cfg.eval_cache_size,
        policy=precision,
    )
    acc = _run_metric_pass(
        fwd, dataset, kind="kitti", iters=iters, batch_size=batch_size,
        mesh=mesh, pad_mode="kitti", bucket=cfg.eval_pad_bucket,
        with_valid=True, num_workers=cfg.num_workers,
        depth=cfg.device_prefetch,
    )
    if do_reduce:
        acc = allreduce_sum_across_hosts(acc)
    m = metrics_mod.finalize("kitti", acc)
    _print_main(f"Validation KITTI: {m['epe']:f}, {m['f1']:f}")
    return {"kitti-epe": m["epe"], "kitti-f1": m["f1"]}


def create_sintel_submission(
    model: RAFT,
    variables: dict,
    data_cfg: Optional[DataConfig] = None,
    iters: int = 32,
    warm_start: bool = False,
    output_path: str = "sintel_submission",
    write_png: bool = False,
    mesh=None,
    precision: Optional[str] = None,
) -> None:
    """Write Sintel leaderboard .flo files (reference: evaluate.py:22-57),
    optionally warm-starting each sequence from the previous frame's
    forward-interpolated low-res flow.

    Full-field pulls are unavoidable here — the deliverable IS the flow
    field — but they ride the :class:`AsyncDrain` worker: dispatch of
    frame N+1 overlaps the device→host pull and file write of frame N.
    The warm-start splat runs ON DEVICE
    (``ops/warmstart.forward_interpolate_jax``): the next frame's
    ``flow_init`` is the jitted forward-splat of this frame's device
    ``flow_lr``, so the serial per-frame device→host pull the host
    cKDTree splat used to force (the last JGL008 allowlist entry) is
    gone — the warm-start chain never leaves the device.

    On a pod EVERY process runs the forwards (with a global mesh the
    SPMD program requires all participants — an early return on non-main
    processes would deadlock process 0's first sharded forward), but
    only the main process touches the filesystem: N hosts writing the
    same files into shared storage interleave. Without a mesh the
    forwards are host-local (no collectives), so non-main processes
    skip the pass entirely instead of computing results nobody keeps."""
    write = is_main_process()
    if mesh is None and not write:
        return
    cfg = data_cfg or DataConfig()
    fwd = ShapeCachedForward(
        model, variables, mesh=mesh, cache_size=cfg.eval_cache_size,
        policy=precision,
    )
    for dstype in ("clean", "final"):
        dataset = ds_mod.MpiSintel(
            None, split="test", root=cfg.root_sintel, dstype=dstype
        )
        flow_prev, sequence_prev = None, None
        with SamplePrefetcher(
            dataset, num_workers=cfg.num_workers
        ) as samples, AsyncDrain(depth=cfg.device_prefetch) as drain:
            for s in samples:
                sequence, frame = s["extra_info"]
                if sequence != sequence_prev:
                    flow_prev = None
                img1 = np.asarray(s["image1"], np.float32)[None]
                img2 = np.asarray(s["image2"], np.float32)[None]
                padder = InputPadder(img1.shape, divisor=_pad_divisor(mesh))
                img1, img2 = _pad_host(padder.pad_spec, img1, img2)
                flow_lr, flow_up = fwd.forward_device(
                    img1, img2, iters, flow_init=flow_prev
                )
                if warm_start:
                    # The next frame's flow_init is this frame's
                    # forward-splatted low-res flow — computed on
                    # device, handed straight back to the next
                    # forward_device call as a device array. No host
                    # round-trip in the warm-start chain.
                    flow_prev = _device_splat(flow_lr)
                if write:
                    drain.submit(
                        flow_up,
                        _sintel_writer(
                            padder, output_path, dstype, sequence, frame,
                            write_png,
                        ),
                    )
                sequence_prev = sequence


def _sintel_writer(
    padder: InputPadder, output_path: str, dstype: str, sequence: str,
    frame: int, write_png: bool,
):
    """Drain callback: unpad on host (pure slicing) and write the frame's
    .flo (and optional viz png). Runs on the AsyncDrain worker thread,
    overlapped with the next frame's device compute."""

    def write_cb(flow_up: np.ndarray) -> None:
        flow = padder.unpad(flow_up)[0]
        out_dir = os.path.join(output_path, dstype, sequence)
        os.makedirs(out_dir, exist_ok=True)
        write_flo(os.path.join(out_dir, f"frame{frame + 1:04d}.flo"), flow)
        if write_png:
            import cv2

            png_dir = os.path.join(output_path + "_png", dstype, sequence)
            os.makedirs(png_dir, exist_ok=True)
            cv2.imwrite(
                os.path.join(png_dir, f"frame{frame + 1:04d}.png"),
                flow_to_image(flow, convert_to_bgr=True),
            )

    return write_cb


def create_kitti_submission(
    model: RAFT,
    variables: dict,
    data_cfg: Optional[DataConfig] = None,
    iters: int = 24,
    output_path: str = "kitti_submission",
    write_png: bool = False,
    mesh=None,
    precision: Optional[str] = None,
) -> None:
    """Write KITTI leaderboard 16-bit pngs (reference: evaluate.py:60-87).
    All processes compute when a global mesh forces lockstep, only main
    writes (see create_sintel_submission). Full-field pulls ride the
    AsyncDrain worker behind dispatch."""
    write = is_main_process()
    if mesh is None and not write:
        return
    cfg = data_cfg or DataConfig()
    dataset = ds_mod.KITTI(None, split="testing", root=cfg.root_kitti)
    fwd = ShapeCachedForward(
        model, variables, mesh=mesh, cache_size=cfg.eval_cache_size,
        policy=precision,
    )
    if write:
        os.makedirs(output_path, exist_ok=True)
        if write_png:
            os.makedirs(output_path + "_png", exist_ok=True)
    with SamplePrefetcher(
        dataset, num_workers=cfg.num_workers
    ) as samples, AsyncDrain(depth=cfg.device_prefetch) as drain:
        for s in samples:
            (frame_id,) = s["extra_info"]
            img1 = np.asarray(s["image1"], np.float32)[None]
            img2 = np.asarray(s["image2"], np.float32)[None]
            padder = InputPadder(
                img1.shape, mode="kitti", divisor=_pad_divisor(mesh),
                bucket=cfg.eval_pad_bucket,
            )
            img1, img2 = _pad_host(padder.pad_spec, img1, img2)
            _, flow_up = fwd.forward_device(img1, img2, iters)
            if write:
                drain.submit(
                    flow_up,
                    _kitti_writer(padder, output_path, frame_id, write_png),
                )


def _kitti_writer(
    padder: InputPadder, output_path: str, frame_id: str, write_png: bool
):
    """Drain callback: unpad + write one KITTI 16-bit submission png."""

    def write_cb(flow_up: np.ndarray) -> None:
        flow = padder.unpad(flow_up)[0]
        write_flow_kitti(os.path.join(output_path, frame_id), flow)
        if write_png:
            import cv2

            cv2.imwrite(
                os.path.join(output_path + "_png", frame_id),
                flow_to_image(flow, convert_to_bgr=True),
            )

    return write_cb


def validate_synthetic(
    model: RAFT, variables: dict, data_cfg: Optional[DataConfig] = None,
    iters: int = 12, batch_size: int = 4, size_hw: tuple[int, int] = (96, 128),
    length: int = 32, mesh=None, style: Optional[str] = None,
    seed: int = 999, precision: Optional[str] = None,
) -> dict:
    """EPE on a HELD-OUT procedural split (seed distinct from the
    training fallback's seed=0) so data-free runs (`--synthetic_ok`,
    `--validation synthetic`) get a genuine generalization signal, not a
    training-set echo. No reference analogue — the reference always
    validates on real datasets (evaluate.py:90-182).

    ``style`` defaults to the training distribution
    (``data_cfg.synthetic_style``) so `--validation synthetic` measures
    generalization on the data the run trained on. ``style="rigid"``
    additionally reports a boundary-band EPE (pixels within 3 px of a
    flow discontinuity) and its complement — the metric pair on which
    guided (NCUP) upsampling is expected to beat bilinear (reference
    claim: core/upsampler.py:75-210). The band mask is computed on the
    staging thread (cv2.dilate) and shipped to device with the batch.

    ``seed`` keys the held-out split's content. The default (999) is the
    historical held-out split; multi-seed callers
    (scripts/ncup_vs_bilinear.py's bootstrap CI) evaluate the same
    checkpoint over several disjoint splits to put error bars on the
    quality claim. Keep any explicit seed away from the training
    fallback's seed=0."""
    from raft_ncup_tpu.data.synthetic import (
        SyntheticFlowDataset,
        flow_boundary_mask,
    )

    if style is None:
        style = data_cfg.synthetic_style if data_cfg else "smooth"
    prefix = "synthetic" if style == "smooth" else f"synthetic_{style}"
    dataset = SyntheticFlowDataset(size_hw, length=length, seed=seed,
                                   style=style)
    dataset, n, do_reduce = _shard_for_validation(dataset, mesh)
    if n == 0:
        # Mirror the real-data validators: an empty agreed length (e.g.
        # length=0, or more hosts than frames) must skip, not divide by
        # zero below (ADVICE r5).
        _print_main("validate_synthetic: no frames after sharding, skipping")
        return {}
    cfg = data_cfg or DataConfig()
    fwd = ShapeCachedForward(
        model, variables, mesh=mesh, cache_size=cfg.eval_cache_size,
        policy=precision,
    )
    kind = "epe_band" if style == "rigid" else "epe"
    acc = _run_metric_pass(
        fwd, dataset, kind=kind, iters=iters, batch_size=batch_size,
        mesh=mesh,
        band_fn=flow_boundary_mask if style == "rigid" else None,
        num_workers=cfg.num_workers, depth=cfg.device_prefetch,
    )
    if do_reduce:
        acc = allreduce_sum_across_hosts(acc)
    m = metrics_mod.finalize(kind, acc)
    out = {prefix: m["epe"]}
    if style == "rigid":
        out[f"{prefix}_bnd"] = m["bnd"]
        out[f"{prefix}_interior"] = m["interior"]
        _print_main(
            f"Validation Synthetic[{style}] EPE: {m['epe']:f}, "
            f"boundary: {m['bnd']:f}, "
            f"interior: {m['interior']:f}"
        )
    else:
        _print_main(f"Validation Synthetic EPE: {m['epe']:f}")
    return out


def validate_synthetic_rigid(
    model: RAFT, variables: dict, data_cfg: Optional[DataConfig] = None,
    **kwargs,
) -> dict:
    """Held-out piecewise-rigid split with boundary-band EPE (see
    :func:`validate_synthetic`)."""
    return validate_synthetic(
        model, variables, data_cfg, style="rigid", **kwargs
    )


VALIDATORS = {
    "chairs": validate_chairs,
    "sintel": validate_sintel,
    "sintel_warm": validate_sintel_warm,
    "kitti": validate_kitti,
    "synthetic": validate_synthetic,
    "synthetic_rigid": validate_synthetic_rigid,
}
