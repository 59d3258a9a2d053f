"""Ask the chip's compiler, without the chip.

The TPU compiler is installed in the CPU sandbox and compiles for a
*described* v5e (on-chip-measurement guide, section 2). Interpret-mode
tests guard the kernels' math and tests/test_pallas_lowering.py stops at
Pallas -> Mosaic MLIR; every refusal the first chip bring-up met (a
dynamic sublane start Mosaic could not prove aligned, a scoped-VMEM
overflow the dispatch gate had admitted) happens one layer further down,
in the compile these tests run. Nothing executes; a pass is not a chip
run.

All of it lives in this ONE file: the worker that runs it loads libtpu
and keeps its lock until it exits, so a second file on another xdist
worker could describe no topology. The topology is described inside a
fixture, never at import, and every compile happens in the test's own
process.

What the file costs, and why its place in the run matters. It is ten
minutes of the suite and, being one file, one worker's work: the suite's
wall cannot go under its length. tests/conftest.py therefore hands it
out first (`LONGEST_FILES_FIRST`). The whole programs (the Sintel
train step at the published batch 6, the eval cell's batch-8 forward,
since PR 39 its `bf16_infer` twin at batch 16: 67 s alone)
are compiled ONCE each, in module-scoped fixtures, and every property of
a program is a test of its own name on that one compile. The train step
is 4 s of tracing, 1 s of lowering and some 600 CPU-seconds in the TPU
compiler (its memory report is written ~20 s in, PR 26: what follows is
code generation), spread over whatever cores the host has free: 114 to
190 s of wall alone on 8 cores, about 300 s as the first test of the
suite, 607 s when it started minutes in and ended beside five busy
workers (PR 28). The eval forward is 94 s alone and 240-275 s in the
suite, the 14 kernel cases 40 s alone and 90 s there. The 1080p cell's
forward (PR 32: batch 4, 1080x1920, the Pallas lookup; 32 iterations, the
loop body is compiled once whatever their number) is 60-75 s alone. The
whole programs come first in the file, the kernel cases, seconds each,
last: they are what is left when the worker asks for its next file.
"""

import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from raft_ncup_tpu.ops import corr
from raft_ncup_tpu.ops import corr_pallas as cpk
from raft_ncup_tpu.ops import nconv_pallas as npk

C, RADIUS, LEVELS = 256, 4, 4  # flagship fnet width and lookup geometry

# Every distinct nconv2d site (H, W, k, Cin, Cout) the flagship NCUP
# stack issues for a 368x768 frame (enumerated by tracing the forward).
NCUP_SITES_368x768 = [
    (368, 768, 5, 1, 2),
    (368, 768, 5, 2, 2),
    (184, 384, 5, 2, 2),
    (368, 768, 3, 4, 2),
    (368, 768, 1, 2, 1),
]
# More shapes the gate admits: the eval frame's sites and a 1080p plane.
NCONV_ADMITTED = [
    (440, 1024, 5, 2, 2),
    (220, 512, 5, 2, 2),
    (1088, 1920, 5, 2, 2),
]
# A row too wide for one strip: the gate must say no (the compiler does
# too, after a minute — which is why the gate's answer is what is tested).
NCONV_REJECTED = (64, 8192, 5, 2, 2)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it off around these.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compile_corr(sds, h, w, dtype):
    """corr_lookup_pallas over the whole 4-level pyramid of an (h, w)
    1/8-resolution feature map; returns (dispatch tally, compiled text)."""
    cpk.reset_dispatch_counts()
    fn = jax.jit(
        lambda a, b, c: cpk.corr_lookup_pallas(
            a, b, c, RADIUS, LEVELS, False, dtype
        )
    )
    feat = sds((1, h, w, C))
    text = fn.lower(feat, feat, sds((1, h, w, 2))).compile().as_text()
    return cpk.dispatch_counts(), text


def _compile_nconv(sds, h, w, k, cin, cout):
    fn = jax.jit(
        lambda d, c, wt, b: npk.nconv2d_fused(d, c, wt, b, 1e-20, False)
    )
    plane = sds((2, h, w, cin))
    return fn.lower(
        plane, plane, sds((k, k, cin, cout)), sds((cout,))
    ).compile().as_text()


def _abstract(sds, tree):
    return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)


class Program(NamedTuple):
    """What one compile for the described chip hands to the tests of its
    properties (the executable itself is let go)."""

    text: str  # the optimised module
    temp_gib: float  # memory_analysis().temp_size_in_bytes
    contract_forms: dict  # ops/corr.py's tally of the program's trace


def _program(compiled) -> Program:
    """Called where the program was traced last: every `volume` lookup
    writes all four levels of the tally."""
    temp = compiled.memory_analysis().temp_size_in_bytes
    return Program(compiled.as_text(), temp / 2**30, corr.contract_forms())


@pytest.fixture(scope="module")
def train_program(sds) -> Program:
    """`train.py --stage sintel` as published: batch 6, crop 368x768, 12
    iterations, float32, uint8 images as the loader ships them: forward,
    the lookup's backward into the volume (a pair of transposed
    contractions since PR 25), AdamW. One compile, at jax's default
    precision (minutes less than `highest`, the same buffers); batch 2,
    which this file compiled until PR 25, is left to `chip_smoke.py`."""
    from raft_ncup_tpu.config import TrainConfig, flagship_config
    from raft_ncup_tpu.models.raft import RAFT
    from raft_ncup_tpu.parallel.step import make_train_step
    from raft_ncup_tpu.training.state import create_train_state

    batch = 6
    model_cfg = flagship_config(dataset="sintel", mixed_precision=False)
    train_cfg = TrainConfig(
        stage="sintel", batch_size=batch, image_size=(368, 768), iters=12,
        num_steps=10,
    )
    state = _abstract(sds, jax.eval_shape(lambda: create_train_state(
        jax.random.PRNGKey(0), model_cfg, train_cfg,
        image_shape=(1, 64, 96, 3),
    )[1]))
    images = (batch, 368, 768, 3)
    data = {
        "image1": sds(images, jnp.uint8), "image2": sds(images, jnp.uint8),
        "flow": sds((batch, 368, 768, 2)), "valid": sds((batch, 368, 768)),
    }
    rng = _abstract(sds, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    step = make_train_step(RAFT(model_cfg), train_cfg, mesh=None)
    return _program(step.lower(state, data, rng).compile())


@pytest.fixture(scope="module")
def eval_program(sds) -> Program:
    """The `eval_sintel_nc` cell's program: raft_nc_dbl, `corr_impl`
    "volume" (the default), 8x440x1024 (padded Sintel frames), 32
    iterations, float32 with every product at `highest` (as
    `benchmark/configs/` states). The same model at batch 1 and default
    precision, which this file also compiled until PR 28 to see it under
    2 GiB, is the weaker compile, and `chip_smoke.py`'s eval phase runs
    it on the chip itself."""
    from raft_ncup_tpu.config import flagship_config
    from raft_ncup_tpu.models.raft import RAFT

    model = RAFT(flagship_config(dataset="sintel"))
    assert model.cfg.corr_impl == "volume"
    variables = _abstract(sds, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), (1, 64, 96, 3))
    ))
    img = sds((8, 440, 1024, 3))
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(
            lambda v, a, b: model.apply(v, a, b, iters=32, test_mode=True)
        ).lower(variables, img, img).compile()
    return _program(compiled)


class HdProgram(NamedTuple):
    """The 1080p cell's compile: the program, what it asks of the device
    with its arguments, and the lookup's trace-time dispatch tally."""

    text: str
    temp_and_arguments_gib: float
    tiers: dict


@pytest.fixture(scope="module")
def hd_program(sds) -> HdProgram:
    """The `eval_1080p_nc` cell's program (PR 32): raft_nc_dbl with
    `corr_impl="pallas"`, 4x1080x1920 (1080 is a multiple of 8: the
    padder adds nothing, and the 1/8 map is 135x240), 32 iterations, float32 with every product at `highest`. The model asks
    the runtime whether it is on a TPU before it hands the kernels to
    Mosaic (elsewhere they run interpreted); a compile for a described
    chip answers for it here, in the test, as
    tests/test_pallas_lowering.py does."""
    from raft_ncup_tpu.config import flagship_config
    from raft_ncup_tpu.models.raft import RAFT
    from raft_ncup_tpu.utils import runtime

    model = RAFT(flagship_config(dataset="sintel", corr_impl="pallas"))
    variables = _abstract(sds, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), (1, 64, 96, 3))
    ))
    img = sds((4, 1080, 1920, 3))
    patch = pytest.MonkeyPatch()
    patch.setattr(runtime, "is_tpu_backend", lambda: True)
    cpk.reset_dispatch_counts()
    try:
        with jax.default_matmul_precision("highest"):
            compiled = jax.jit(
                lambda v, a, b: model.apply(v, a, b, iters=32, test_mode=True)
            ).lower(variables, img, img).compile()
    finally:
        patch.undo()
    memory = compiled.memory_analysis()
    asked = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    return HdProgram(compiled.as_text(), asked / 2**30, cpk.dispatch_counts())


@pytest.fixture(scope="module")
def eval_bf16_program(sds) -> Program:
    """The `eval_sintel_nc_bf16` cell's program (PR 39): the same model
    under `bf16_infer` at batch 16 (what the halved volume buys), 440x1024,
    32 iterations, `highest` for the float32 pins (bfloat16 operands ignore
    it). 67 s alone; one compile for its cases."""
    import dataclasses

    from raft_ncup_tpu.config import flagship_config
    from raft_ncup_tpu.models.raft import RAFT

    model = RAFT(dataclasses.replace(
        flagship_config(dataset="sintel"), precision="bf16_infer"
    ))
    variables = _abstract(sds, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), (1, 64, 96, 3))
    ))
    img = sds((16, 440, 1024, 3))
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(
            lambda v, a, b: model.apply(v, a, b, iters=32, test_mode=True)
        ).lower(variables, img, img).compile()
    return _program(compiled)


@pytest.fixture(scope="module")
def kitti_lookup_program(sds) -> Program:
    """The `volume` lookup ALONE at `eval_kitti_nc`'s largest grid (PR 47):
    two `[8,47,156,256]` feature maps through `build_loop_pyramid` and a
    `scan` of 24 lookups, as the refinement loop runs them, float32 at
    `highest`. ~10 s where the whole KITTI forward is 85-100 (a scratch
    compile of it gave the same loop fusions and 4.984 GiB of temporaries
    for the parent's 4.964: PERF.md section 6, PR 47). 156 -> 78 -> 39 -> 19
    columns: no level's width is a multiple of 8, the case
    `ops/corr.py::stored_width` is for."""

    def lookups(fmap1, fmap2, coords):
        pyramid = corr.build_loop_pyramid(fmap1, fmap2, LEVELS)

        def body(c, _):
            out = corr.corr_lookup(pyramid, c, RADIUS)  # every channel is used
            return c + 0.01 * out.reshape(*out.shape[:3], -1, 2).sum(-2), None

        return jax.lax.scan(body, coords, None, length=24)[0]

    corr.reset_contract_forms()
    feat = sds((8, 47, 156, C))
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(lookups).lower(feat, feat, sds((8, 47, 156, 2))).compile()
    return _program(compiled)


def _loop_computations(text: str) -> dict:
    """{name: body text} of every computation reachable from a `while`'s
    `body=` through `calls=` (fusions) and nested loops."""
    bodies, called = {}, {}
    for comp, body in re.findall(r"^(?:ENTRY )?(%[\w.\-]+) \(.*?\{$(.*?)^\}", text, re.M | re.S):
        bodies[comp] = body
        called[comp] = re.findall(r"(?:calls|body|condition)=(%[\w.\-]+)", body)
    in_loop, todo = set(), re.findall(r"body=(%[\w.\-]+)", text)
    while todo:
        comp = todo.pop()
        if comp not in in_loop:
            in_loop.add(comp)
            todo += called[comp]
    return {comp: bodies[comp] for comp in in_loop}


def _ncup_plane_convolutions(text: str, planes: int, h: int, w: int) -> list:
    """Lines of the compiled text with a `convolution` that has an operand
    of NCUP's plane shape: `planes` full-resolution frames with at most 4
    channels. Since PR 27 those layers are tap sums on the vector units
    (`ops/nconv.py::tap_form`); the weights-estimation net's convolutions
    run at 1/4 resolution with 32-130 channels and are not matched."""
    plane = re.compile(rf"f32\[{planes},{h},{w},[1-4]\]")
    return [
        line.strip()[:200] for line in text.splitlines()
        if " convolution(" in line and plane.search(line)
    ]


def _convolutions(text: str):
    """(computation, line, [result shape, *operand shapes]) of every
    `convolution` (a `dot_general` is one too, on the TPU) of a compiled
    module's text. A line names its operands; their shapes are on the lines
    that define them."""
    shape_of = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]*\])", text))
    comp = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
        if " convolution(" not in line:
            continue
        result = re.search(r" = (\w+\[[\d,]*\])", line).group(1)
        operands = re.findall(r"%[\w.\-]+", line.split(" convolution(")[1].split(")")[0])
        yield comp, line, [result, *(shape_of[o] for o in operands)]


def _dims(shape: str) -> list:
    return [int(d) for d in shape[shape.index("[") + 1 : -1].split(",") if d]


def _thin_side_convolutions(text: str, h: int, w: int) -> list:
    """Lines of the compiled text with a `convolution` whose result or an
    operand is an (h, w) plane with a 2-wide feature side: the update
    block's `convf1` (7x7, 2 -> 128) and `flow_head.conv2` (3x3, 256 -> 2)
    as they were until PR 29, a 128-wide MXU tile filled to a sixty-fourth
    per kernel tap. Folded (`nn/layers.py::conv_form`), the same planes
    carry 98 and 18 features."""

    def thin(shape: str) -> bool:
        dims = _dims(shape)
        return len(dims) == 4 and h in dims and w in dims and 2 in dims

    return [
        line.strip()[:200] for _, line, shapes in _convolutions(text)
        if any(thin(s) for s in shapes)
    ]


def _stem_convolutions(text: str, h: int, w: int) -> list:
    """Lines of the compiled text with a `convolution` that has the
    encoders' stem among its shapes as it was until PR 48: the (h, w) frame
    with its 3 channels as the input feature dimension, or the `[7,7,3,64]`
    kernel (an operand forward, the result of its cotangent). Phased
    (`nn/layers.py::conv_form` at stride 2), the convolution reads the
    frame's 2x2 phases with their four column shifts, 48 features on an
    (h / 2 + 3, w / 2) plane, and a `[4,1,48,64]` kernel."""

    def stem(shape: str) -> bool:
        dims = _dims(shape)
        return len(dims) == 4 and 3 in dims and (
            (h in dims and w in dims) or sorted(dims) == [3, 7, 7, 64]
        )

    return [
        line.strip()[:200] for _, line, shapes in _convolutions(text)
        if any(stem(s) for s in shapes)
    ]


def _gru_gate_convolutions(text: str) -> dict:
    """{(inside a `while` body?, contraction width): count} over the
    `convolution`s of the compiled text that have one of the GRU gates'
    5-tap kernels among their operands (forward, input cotangent) or as
    their result (kernel cotangent): a shape that is, 1s aside, 5 taps by
    128 gate outputs by `width` input rows. The full `[h, inp, motion]`
    width is 384; since PR 31 the loop's gates contract `[h, motion]`, 256
    wide, and what they make of the 128 context channels is convolved once,
    outside every loop. A loop body is every computation reachable from a
    `while`'s `body=` through `calls=` (fusions) and nested loops."""
    in_loop = _loop_computations(text)
    found: dict = {}
    for comp, _, shapes in _convolutions(text):
        for shape in shapes:
            dims = sorted(d for d in _dims(shape) if d != 1)
            if len(dims) == 3 and dims[:2] == [5, 128]:
                key = (comp in in_loop, dims[2])
                found[key] = found.get(key, 0) + 1
    return found


def _record_temp(record_property, program: Program) -> float:
    record_property("temp_size_gib", round(program.temp_gib, 3))
    print(f"temp_size {program.temp_gib:.3f} GiB")
    return program.temp_gib


def test_sintel_train_step_has_no_convolution_over_an_ncup_plane(
    train_program,
):
    """Since PR 27 NCUP's convolutions are float32 tap sums on the vector
    units, forward and both cotangents: no `convolution` over its 12
    planes of 368x768 is left in the step."""
    assert _ncup_plane_convolutions(train_program.text, 12, 368, 768) == []


def test_sintel_train_step_has_no_convolution_with_a_2_wide_side(
    train_program,
):
    """Since PR 29 the update block's two 2-channel convolutions are one
    product each with their taps folded into the thin side, forward,
    rematerialised and both cotangents: no `convolution` of the step has a
    2-wide feature side on the 46x96 plane (368x768 / 8)."""
    assert _thin_side_convolutions(train_program.text, 46, 96) == []


def test_sintel_train_step_has_no_convolution_with_a_3_wide_input(
    train_program,
):
    """Since PR 48 the encoders' 7x7 stride-2 stems are stride-1
    convolutions over the crop's phases, forward, rematerialised and kernel
    cotangent: no `convolution` of the step reads a 368x768 frame's 3
    channels or the `[7,7,3,64]` kernel; fnet's reads 12 frames' 187x384
    plane of 48 features."""
    assert _stem_convolutions(train_program.text, 368, 768) == []
    assert "f32[12,187,384,48]" in train_program.text


def test_sintel_train_step_loops_convolve_no_context_features(train_program):
    """Since PR 31 the GRU's context terms are constants of the checkpointed
    scan body: the forward, rematerialised and backward loops hold the
    gates over `[h, motion]` alone (256 wide; no 384-wide kernel is left
    anywhere), and the `inp` rows' products run ONCE a step outside the
    loops, not 12 times in them: 6 forward, 6 input cotangents, 6 kernel
    cotangents (the loop's cotangent of each term is summed first)."""
    found = _gru_gate_convolutions(train_program.text)
    assert {width for in_loop, width in found if in_loop} == {256}
    assert {key: n for key, n in found.items() if not key[0]} == {(False, 128): 18}


def test_sintel_train_step_backward_loop_runs_no_second_lookup_contraction(
    train_program,
):
    """Since PR 38 the checkpointed scan body keeps the lookup's 324 planes
    and the weights net's two hidden convolution outputs by name
    (`utils/remat.py`): of the lookup the backward loop computes the axis
    weights again and nothing else (no sum over a pyramid level, no
    product), of the weights net the 32 -> 2 head alone; the update
    block's second forward is still there."""
    again = [
        line for body in _loop_computations(train_program.text).values()
        for line in body.splitlines() if "rematted_computation/" in line
    ]
    assert [line for line in again if "rematted_computation/raft.update_block/" in line]
    assert [line for line in again if "rematted_computation/raft.corr_lookup/" in line]
    assert not [line for line in again if re.search(
        r"rematted_computation/raft\.corr_lookup/(reduce_sum|mul|dot_general)", line)]
    assert not [line for line in again if re.search(
        r"rematted_computation/\S*weights_est_net/conv\d+/conv_general_dilated", line)]


def test_sintel_train_step_traces_no_forward_only_contraction(train_program):
    """The step differentiates its lookup and says so
    (`build_corr_pyramid(differentiated=True)`): every level keeps the form it had
    before PR 42, whatever it is stored in, and the step's lowered module
    is the parent's letter for letter (hashes: PERF.md section 6, PR 42)."""
    assert train_program.contract_forms == {
        f"level{lvl}": "multiply_reduce/float32" for lvl in range(4)
    }


def test_sintel_train_step_temporaries_stay_under_8_gib(
    train_program, record_property
):
    """Since PR 26 rematerialises the encoders and takes NCUP's kernel
    gradient tap by tap, the step asks 5.4 GiB at `highest` (14.8 GiB
    before; at batch 2 2.1 GiB, 6.7 before); at one pass, as compiled
    here, 4.84 GiB in every run of PR 28 (5.42 in the driver's run of PR
    27's tree: the compiler's answer is not the same on every host).
    Since PR 38 the loop keeps 0.9 GB of named values stacked over its 12
    iterations (`[12,6,46,96,324]`, `[12,6,92,192,64]`,
    `[12,6,92,192,32]`) and the figure is 4.833 GiB: they fit under the
    peak the step already had."""
    assert _record_temp(record_property, train_program) < 8.0


def test_eval_cell_forward_has_no_gather(eval_program):
    """The lookup inside the loop is dense arithmetic (PR 25): no gather
    is left anywhere in the forward."""
    assert " gather(" not in eval_program.text


def test_eval_cell_forward_convolves_but_never_over_an_ncup_plane(
    eval_program,
):
    """The encoders and the update block are convolutions; NCUP, once per
    forward over 16 planes of 440x1024, is tap sums on the vector units
    (PR 27): no `convolution` has its plane shape."""
    assert " convolution(" in eval_program.text
    assert _ncup_plane_convolutions(eval_program.text, 16, 440, 1024) == []


def test_eval_cell_forward_has_no_convolution_with_a_2_wide_side(
    eval_program,
):
    """`convf1` and `flow_head.conv2`, 2.62 of the 20.9 ms an iteration of
    a batch of 8 cost (PR 28's ledger lines), are folded (PR 29): no
    `convolution` has a 2-wide feature side on the 55x128 plane."""
    assert _thin_side_convolutions(eval_program.text, 55, 128) == []


def test_eval_cell_forward_has_no_convolution_with_a_3_wide_input(
    eval_program,
):
    """`raft.fnet` and `raft.cnet`'s `conv1`, 40.9 ms of a batch of 8 as a
    strided `conv_general_dilated` over 3 channels (PR 47's ledger lines:
    2-5% of their six-pass peak), are phased (PR 48): no `convolution`
    reads a 440x1024 frame's 3 channels or the `[7,7,3,64]` kernel; each
    reads ONE 223x512 stack of 48 features, 16 frames for fnet and 8 for
    cnet (the barrier in `_conv_phased_in`: four 12-feature stacks would be
    four relayouts)."""
    assert _stem_convolutions(eval_program.text, 440, 1024) == []
    assert "f32[16,223,512,48]" in eval_program.text
    assert "f32[8,223,512,48]" in eval_program.text
    relayouts = re.findall(r"= f32\[(?:8|16),223,512,12\]\S* copy\(", eval_program.text)
    assert relayouts == []


def test_eval_cell_forward_loop_convolves_no_context_features(eval_program):
    """Since PR 31: the six gates' share of the context features before the
    loop, once; in the loop body six gate convolutions over `[h, motion]`;
    no convolution of the program contracts the full 384."""
    assert _gru_gate_convolutions(eval_program.text) == {
        (False, 128): 6, (True, 256): 6,
    }


def test_eval_cell_forward_temporaries_stay_under_6_gib(
    eval_program, record_property
):
    """The program's temporaries leave most of the chip free (PR 25: 4.54
    GiB; the gather form was 5.14 GiB; unchanged by PR 27)."""
    assert _record_temp(record_property, eval_program) < 6.0


def test_eval_bf16_cell_forward_temporaries_stay_under_9_gib(
    eval_bf16_program, record_property
):
    """Batch 16 under `bf16_infer`: 7.332 GiB of temporaries (my compile,
    PR 39) where float32 at batch 8 asks 4.540: the volume is the same
    2.11 GB, the float32 widenings and 32 frames' activations the rest."""
    assert _record_temp(record_property, eval_bf16_program) < 9.0


def test_eval_bf16_cell_forward_widens_level_0_inside_its_contraction(
    eval_bf16_program, record_property
):
    """What decides the lookup's cost at one pass. The volume is stored in
    bfloat16 and the lookup computes in float32 (P7), so every level is
    widened every iteration, and since PR 42 none of levels 0-2 into a
    buffer of its own (until then levels 1-3 were: 1.01 GB written and read
    again an iteration). Level 0 (16 x 7040 x 55 x 128: 3.17 GB as
    float32): no instruction of the module has that float32 shape, and the
    loop's x contraction is one `convolution` fusion that reads the
    bfloat16 level and writes `f32[16,7040,55,9]`. Levels 1-2 take the tap
    sums (`ops/corr.py::_tap_sums`): one reduction of nine operands reads
    `bf16[16,7040,27,64]` / `[13,32]` as stored, queries in the lanes
    (`{1,3,2,0}`: the layout the two head rows' multiply + reduce asks for;
    rows in the lanes would pad 64 to 128), and no float32 tensor of a
    level's size exists in the loop; the two head rows are widened by a
    small fusion of their own (`f32[16,7040,2,64]`). Level 3 (96 elements a
    query, under `TAP_SUMS_MIN_SIZE`) keeps the multiply + reduce form and
    the only `convert` that is an instruction of the loop body itself:
    0.043 GB an iteration. (The training step keeps the multiply + reduce
    form at every level and materialises its widenings, PERF.md section
    7.)"""
    assert eval_bf16_program.contract_forms == {
        "level0": "dot/bfloat16", "level1": "tap_sums/bfloat16",
        "level2": "tap_sums/bfloat16", "level3": "multiply_reduce/bfloat16",
    }
    text = eval_bf16_program.text
    assert "f32[16,7040,55,128]" not in text
    loops = _loop_computations(text)
    contraction = [
        body for body in loops.values()
        if "bf16[16,7040,55,128]" in body
        and re.search(r"ROOT \S+ = f32\[16,7040,55,9\]\S* convolution\(", body)
    ]
    assert len(contraction) == 1
    in_loop = "\n".join(loops.values())
    for level in ("27,64", "13,32"):
        assert f"f32[16,7040,{level}]" not in in_loop
        (tap_sums,) = [
            b for b in loops.values()
            if re.search(rf"param_\S+ = bf16\[16,7040,{level}\]\{{1,3,2,0", b)
            and re.search(r"ROOT \S+ = \(f32\[16,7040,\d+\]\S*(, (/\*index=\d+\*/)?f32\[16,7040,\d+\]\S*){8}\) reduce\(", b)
        ]
    # a `convert` that is a fusion's own instruction is part of that fusion's
    # pass; one that is an instruction of the loop body writes its result
    (body,) = [
        loops[name] for name in re.findall(r"body=(%[\w.\-]+)", text)
        if "bf16[16,7040,27,64]" in loops[name]
    ]
    standalone = sorted(re.findall(r"= (f32\[16,7040,\d+,\d+\])\S* convert\(", body))
    assert standalone == ["f32[16,7040,6,16]"]
    # nor does any instruction of the body write a level, or a level less
    # its head rows, as float32
    assert not set(re.findall(r"= (f32\[16,7040,\d+,\d+\])", body)) & {
        f"f32[16,7040,{rows},{cols}]"
        for rows, cols in ((27, 64), (25, 64), (13, 32), (11, 32))
    }
    record_property("levels_widened_by_a_convert_of_their_own", standalone)
    assert " gather(" not in text
    assert _gru_gate_convolutions(text) == {(False, 128): 6, (True, 256): 6}


def test_eval_bf16_cell_forward_pools_and_looks_up_what_a_level_stores(
    eval_bf16_program,
):
    """P6 as the compiler kept it: "each pooled level the float32 mean of
    the level below, rounded" holds only if nothing reads a level BEFORE its
    rounding, and the compiler may drop a cast down and up again inside one
    program (`xla_allow_excess_precision`: a small program that builds the
    pyramid and looks it up in one piece read levels 1-3 unrounded on a v5e,
    PERF.md section 6, PR 39). Here each pooling reads the bfloat16 level
    (three fusions, `bf16[B*7040,h,w,1] -> f32[B*7040,2*(h//2),w,1]`), and
    the four levels are operands of the refinement loop in bfloat16, so the
    lookup inside it can only read what was stored."""
    text = eval_bf16_program.text
    pooled = re.findall(
        r"\(param_\S+: bf16\[112640,(\d+),(\d+),1\]\) -> f32\[112640,(\d+),(\d+),1\]", text
    )
    assert sorted(tuple(map(int, p)) for p in pooled) == [
        (13, 32, 12, 32), (27, 64, 26, 64), (55, 128, 54, 128)
    ]
    (loop,) = [line for line in text.splitlines() if re.search(r"= \(.*bf16\[16,7040,55,128\].*\) while\(", line)]
    carried = loop.split(" while(", 1)[0]
    for level in ("55,128", "27,64", "13,32", "6,16"):
        assert f"bf16[16,7040,{level}]" in carried and f"f32[16,7040,{level}]" not in carried


def _first_stage_windows(text: str, queries: int) -> dict:
    """{stored width: `output_window_bounds`} of the loop's first-stage (y)
    lookup fusions: a multiply + reduce from a level `[8, queries, Hl, Wl]`
    to `[8, queries, 9, Wl]`, whose window the compiler writes on the
    fusion's line."""
    windows = {}
    for body in _loop_computations(text).values():
        for line in body.splitlines():
            m = re.search(
                rf"%multiply_reduce_fusion\S* = f32\[8,{queries},9,(\d+)\]\S* fusion\(.*"
                r'"output_window_bounds":\[([^\]]*)\]', line)
            if m and int(m.group(1)) > 9:
                windows[int(m.group(1))] = [int(b) for b in re.findall(r"\d+", m.group(2))]
    return windows


def test_kitti_lookup_walks_levels_0_to_2_once_an_iteration(
    kitti_lookup_program, record_property
):
    """What PR 47 is for (PR 46's change, asked again). At its own width
    (156, 78) the compiler cuts the nine taps of a level's first stage into
    three blocks of three and walks
    the level once a block (`output_window_bounds` `[3,47,6,1,1]`,
    `[3,23,13,1,1]`; batch in sublanes, `{1,0,3,2}`): 5.16 GB an iteration
    for a 1.72 GB level, the first bottleneck of `eval_kitti_nc` at PR 45.
    Stored at a multiple of 8 the level takes x in sublanes (`{1,3,2,0}`)
    and all nine taps in one block. So: every level of the loop is read at
    its stored width and in that layout, nothing in the loop (nor anything
    the loop carries) has a level's own width, and the first-stage fusions
    of levels 0-2 each hold the 9 in one window (level 3, 5 x 24, is now
    cut in three where 5 x 19 held the nine: 0.03 GB a walk)."""
    assert kitti_lookup_program.contract_forms == {
        "level0": "multiply_reduce@160/float32", "level1": "multiply_reduce@80/float32",
        "level2": "multiply_reduce@40/float32", "level3": "multiply_reduce@24/float32",
    }
    text = kitti_lookup_program.text
    in_loop = "\n".join(_loop_computations(text).values())
    for rows, own, stored in ((47, 156, 160), (23, 78, 80), (11, 39, 40), (5, 19, 24)):
        assert f"f32[8,7332,{rows},{own}]" not in in_loop
        assert f"f32[8,7332,{rows},{stored}]{{1,3,2,0:" in in_loop
        assert f"f32[8,7332,{rows},{stored}]{{1,0,3,2:" not in in_loop
    windows = _first_stage_windows(text, 7332)
    record_property("first_stage_output_window_bounds", windows)
    assert sorted(windows) == [24, 40, 80, 160]
    for stored, rows in ((160, 47), (80, 23), (40, 11)):
        # the level's own pass: all nine taps and every row in one window
        assert 9 in windows[stored] and rows in windows[stored], windows[stored]


def test_kitti_lookup_temporaries_hold_one_level_0_not_two(
    kitti_lookup_program, record_property
):
    """Level 0 is 8 x 7,332 x 47 x 160 x 4 = 1.643 GiB. Its zero columns are
    zero FEATURE columns of `fmap2`, so the product writes it at its stored
    width: 4.932 GiB of temporaries for the parent's 4.910 (the product, its
    relayout and the pooling's relayout of the own columns are one level-0
    buffer each, no two of them live with a third; PR 46's compiles, made
    again by PR 47). A `pad` of the built level is a buffer beside it: 6.093
    GiB."""
    assert _record_temp(record_property, kitti_lookup_program) < 5.5
    assert "f32[8,7332,47,160]" in kitti_lookup_program.text
    assert not re.search(r"= f32\[8,7332,47,160\]\S* pad\(", kitti_lookup_program.text)


def test_hd_cell_forward_fits_the_chip_at_batch_4(hd_program, record_property):
    """Without the volume (5.56 GB a pair at this shape: a batch of 4 could
    not exist) the batch-4 program asks 6.18 GiB of temporaries + 0.21 of
    arguments: under the 13 GiB over which ISSUE 32 would have cut the
    cell's batch to 2, and over the quarter of the chip a cell must fill."""
    record_property("temp_and_arguments_gib", round(hd_program.temp_and_arguments_gib, 3))
    print(f"temp + arguments {hd_program.temp_and_arguments_gib:.3f} GiB")
    assert 4.0 < hd_program.temp_and_arguments_gib < 13.0


def test_hd_cell_forward_dispatches_two_banded_and_two_resident_levels(hd_program):
    """Levels 0-1 (135x240, 67x120) exceed residency and take the banded
    kernel, 2-3 stay resident; nothing falls back to XLA's gather form."""
    assert hd_program.tiers == {
        "kernel": 2, "banded": 2, "fallback": 0, "levels_total": 4,
    }


def test_hd_cell_forward_loop_holds_the_four_kernels_by_name(hd_program):
    """The four Mosaic calls are in the refinement loop's body, each under
    its own name, which is what a device trace and the cell's
    `corr_kernel_ms_per_pair` find them by; none runs outside the loop."""
    loop = "".join(_loop_computations(hd_program.text).values())
    calls = re.findall(r"%(corr_\w+?_l\d)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"", loop)
    assert sorted(calls) == [
        "corr_banded_l0", "corr_banded_l1", "corr_resident_l2", "corr_resident_l3",
    ]
    assert hd_program.text.count("tpu_custom_call") == 4


def test_hd_cell_forward_pads_the_pyramid_once_not_in_the_loop(hd_program):
    """`prepare_lookup` (PR 32): the pooled levels are zero-padded for their
    kernels before the loop; the parent's program padded all four in every
    iteration (the compiler does not hoist a pad that grows its operand).
    The padded shapes: level 0 banded 171x280, level 1 banded 139x160,
    level 2 resident 55x96, level 3 resident 38x72."""
    padded = re.compile(r" = f32\[4,(?:171,280|139,160|55,96|38,72),256\]\S* pad\(")
    loop = "".join(_loop_computations(hd_program.text).values())
    assert len(padded.findall(hd_program.text)) == 4
    assert padded.findall(loop) == []


def test_hd_cell_forward_loop_gathers_rows_of_the_band_sort_only(hd_program):
    """ISSUE 32 asked for no gather in the loop; the banded tier has twelve,
    by design: it sorts a level's queries by band and gathers whole ROWS of
    the query-major operands into that order (features 256 wide, origins,
    fractions, band ids, the chunk table's band column) and the kernel's
    output back. What the property holds: every gather of the loop is such a
    row permutation over the 32,400 queries (32,512 with the last query
    block filled) or the ~260-chunk table, i.e.
    none reads a feature LEVEL, which is what a level fallen back to XLA's
    `grid_sample` form would do (ROADMAP M4)."""
    loop = "".join(_loop_computations(hd_program.text).values())
    gathers = re.findall(r" = (\w+\[[\d,]*\])\S* gather\(", loop)
    assert len(gathers) == 12
    for shape in gathers:
        dims = _dims(shape)
        assert dims[0] == 4 and (dims[1] in (32400, 32512) or (len(dims) == 2 and dims[1] < 300)), shape


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_corr_resident_kernel_compiles_at_the_sintel_crop(sds, dtype):
    """46x96x256 is the 368x768 crop at 1/8 resolution: every level fits
    the resident tier, and all four compile to Mosaic calls."""
    tiers, text = _compile_corr(sds, 46, 96, dtype)
    assert tiers == {
        "kernel": 4, "banded": 0, "fallback": 0, "levels_total": 4,
    }
    assert text.count("tpu_custom_call") == 4


def test_corr_banded_kernel_compiles_at_1080p(sds):
    """136x240x256 (1088x1920 / 8): levels 0-1 exceed residency and take
    the banded kernel, 2-3 stay resident; nothing falls back to XLA."""
    tiers, text = _compile_corr(sds, 136, 240, jnp.float32)
    assert tiers == {
        "kernel": 2, "banded": 2, "fallback": 0, "levels_total": 4,
    }
    assert text.count("tpu_custom_call") == 4


@pytest.mark.parametrize("h,w", [(55, 128), (23, 48)],
                         ids=["eval440x1024", "quarter"])
def test_corr_gate_admitted_levels_compile(sds, h, w):
    """Gate truthfulness for the correlation tiers: whatever mix of
    resident and banded levels fits_vmem / band_plan choose at a shape,
    every level they admit compiles (55x128 is chip_smoke's eval frame)."""
    tiers, text = _compile_corr(sds, h, w, jnp.float32)
    assert tiers["fallback"] == 0
    assert text.count("tpu_custom_call") == tiers["kernel"] + tiers["banded"]


@pytest.mark.parametrize("site", NCUP_SITES_368x768,
                         ids=lambda s: "x".join(map(str, s)))
def test_nconv_fused_compiles_at_every_ncup_site(sds, site):
    h, w, k, cin, cout = site
    assert npk.supported((k, k, cin, cout), 1, 1)
    assert npk.fits_vmem(h, w, cin, cout, k)
    assert "tpu_custom_call" in _compile_nconv(sds, *site)


def test_nconv_gate_rejects_a_row_too_wide_for_one_strip():
    h, w, k, cin, cout = NCONV_REJECTED
    assert npk.supported((k, k, cin, cout), 1, 1)
    assert not npk.fits_vmem(h, w, cin, cout, k)


@pytest.mark.parametrize("site", NCONV_ADMITTED,
                         ids=lambda s: "x".join(map(str, s)))
def test_nconv_gate_admitted_shapes_compile(sds, site):
    """Gate truthfulness for the fused NConv: admitted => compiles."""
    h, w, k, cin, cout = site
    assert npk.fits_vmem(h, w, cin, cout, k)
    assert "tpu_custom_call" in _compile_nconv(sds, *site)
