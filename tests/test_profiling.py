"""The reduction of a device trace (raft_ncup_tpu/utils/profiling.py): all
on handmade events and handmade HLO text, so nothing here needs a capture.
The one test that parses a real ``.xplane.pb`` is with the bridge's tests
(tests/test_observability.py::TestProfilerBridge)."""

import json

import jax
import jax.numpy as jnp
import pytest

from raft_ncup_tpu.inference.costs import CostLedger
from raft_ncup_tpu.utils import profiling as P

LOOKUP, UPDATE, LOOP, FNET = (
    "raft.corr_lookup", "raft.update_block", "raft.refinement", "raft.fnet",
)


@pytest.mark.parametrize("op_name,want", [
    ("jit(fn)/raft.refinement/while/body/closed_call/raft.corr_lookup/gather", LOOKUP),
    ("jit(fn)/raft.refinement/while/body/closed_call/add", LOOP),
    ("jit(fn)/raft.fnet/BasicEncoder/conv1/conv_general_dilated", FNET),
    ("jit(fn)/raft.upsample/raft.metric_head/reduce_sum", "raft.metric_head"),
    ("jit(fn)/div", None),
    ("jit(fn)/aircraft.wing/mul", None),
    # the training step (PR 26): train.* scopes, and the phase of a
    # differentiated program's operations
    ("jit(step)/train.optimizer_update/mul", "train.optimizer_update"),
    ("jit(step)/train.forward_backward/reduce_sum", "train.forward_backward"),
    ("jit(step)/train.forward_backward/jvp(raft.fnet)/checkpoint/Encoder/conv1/conv_general_dilated", FNET),
    ("jit(step)/train.forward_backward/transpose(jvp(raft.fnet))/train.forward_backward/jvp(raft.fnet)"
     "/checkpoint/checkpoint/Encoder/conv1/conv_general_dilated", FNET + ".bwd"),
    ("jit(step)/train.forward_backward/transpose(jvp(raft.fnet))/train.forward_backward/jvp(raft.fnet)"
     "/checkpoint/checkpoint/rematted_computation/Encoder/norm1/mul", FNET + ".remat"),
    ("jit(step)/train.forward_backward/transpose(jvp(raft.refinement))/while/body/closed_call/checkpoint"
     "/raft.update_block/BasicUpdateBlock/gru/convq1/conv_general_dilated", UPDATE + ".bwd"),
    ("jit(step)/train.forward_backward/transpose(jvp(raft.refinement))/while/body/closed_call/checkpoint"
     "/rematted_computation/raft.corr_lookup/eq", LOOKUP + ".remat"),
    ("jit(step)/train.forward_backward/transpose(jvp(raft.refinement))/while/body/closed_call/add_any", LOOP + ".bwd"),
    ("jit(step)/train.forward_backward/transpose(train.forward_backward)/mul", "train.forward_backward.bwd"),
    # the GRU's context terms, once before the loop (PR 31): a scope of their
    # own in the forward, and in the step's backward after the loop's
    ("jit(fn)/raft.gru_context/BasicUpdateBlock.context/gru.context/convz1.context/convz1._conv"
     "/conv_general_dilated", "raft.gru_context"),
    ("jit(step)/train.forward_backward/transpose(jvp(raft.gru_context))/BasicUpdateBlock.context"
     "/gru.context/convz1.context/convz1._conv/conv_general_dilated", "raft.gru_context.bwd"),
    # the convex-mask head (PR 33): its own scope inside raft.upsample, after
    # the loop in test mode, in the loop body of the training forward
    ("jit(fn)/raft.upsample/raft.mask_head/BasicUpdateBlock.mask/mask_conv2/conv_general_dilated",
     "raft.mask_head"),
    ("jit(step)/train.forward_backward/transpose(jvp(raft.refinement))/while/body/closed_call/checkpoint"
     "/rematted_computation/raft.upsample/raft.mask_head/BasicUpdateBlock.mask/mask_conv1"
     "/conv_general_dilated", "raft.mask_head.remat"),
    ("jit(fn)/raft.upsample/reduce_sum", "raft.upsample"),
    # StreamEngine's slot-table step (PR 30): stream.* around the forward's raft.*
    ("jit(step)/stream.warmstart_splat/argmin", "stream.warmstart_splat"),
    ("jit(step)/stream.slot_gather/gather", "stream.slot_gather"),
    ("jit(step)/stream.anomaly_scatter/scatter", "stream.anomaly_scatter"),
    ("jit(step)/upstream.thing/mul", None),
    # the pallas lookup's own scopes (PR 32): a dotted name is ONE scope,
    # the sort inside raft.corr_lookup, the padded pyramid before the loop
    ("jit(fn)/raft.refinement/while/body/raft.corr_lookup/raft.corr_lookup.band_sort/sort",
     LOOKUP + ".band_sort"),
    ("jit(fn)/raft.corr_lookup.pad_levels/pad", LOOKUP + ".pad_levels"),
    ("jit(fn)/raft.refinement/while/body/raft.corr_lookup/pallas_call", LOOKUP),
])
def test_scope_of_takes_the_innermost_raft_scope(op_name, want):
    assert P.scope_of(op_name) == want


HLO = """HloModule jit_fn, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(fn)/raft.refinement/while/body/raft.corr_lookup/mul" stack_frame_id=3}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(fn)/raft.refinement/while/body/raft.corr_lookup/add"}
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p0.1)
}

%body (s: f32[8]) -> f32[8] {
  %s = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%s), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  ROOT %tanh.7 = f32[8]{0} tanh(%fusion.2), metadata={op_name="jit(fn)/raft.refinement/while/body/raft.update_block/tanh"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %copy-start.3 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)
  %convolution.5 = f32[8]{0} convolution(%a, %a), metadata={op_name="jit(fn)/raft.fnet/conv"}
  ROOT %while.1 = f32[8]{0} while(%convolution.5), condition=%cond, body=%body, metadata={op_name="jit(fn)/raft.refinement/while"}
}
"""


def test_hlo_op_scopes_reads_own_metadata_then_the_called_computation():
    got = P.hlo_op_scopes(HLO)
    assert got["mul.1"] == got["add.1"] == LOOKUP
    assert got["tanh.7"] == UPDATE and got["convolution.5"] == FNET
    assert got["while.1"] == LOOP  # its own op_name wins over its body's
    assert got["fusion.1"] == LOOKUP  # no op_name of its own: most of what it calls
    for unscoped in ("fusion.2", "copy-start.3", "a", "neg.1"):
        assert unscoped not in got


def test_scope_seconds_counts_self_time_and_inherits_the_parents_scope():
    ops = [
        ("copy", None, 0.0, 1.0),              # outside every scope
        ("conv", FNET, 1.0, 3.0),
        ("while", LOOP, 3.0, 13.0),            # parent of everything below
        ("gather", LOOKUP, 3.0, 9.0),
        ("gru", UPDATE, 9.0, 11.0),
        ("fusion.2", None, 11.0, 12.0),        # in the loop, in no inner scope
        ("nested", UPDATE, 9.5, 10.0),         # child of gru: not counted twice
    ]
    got = P.scope_seconds(ops)
    assert got == pytest.approx({
        P.UNSCOPED: 1.0, FNET: 2.0, LOOKUP: 6.0, UPDATE: 2.0,
        LOOP: 2.0,  # the while's own second (12..13) and fusion.2's
    })
    busy = sum(e - s for s, e in P.union((s, e) for _, _, s, e in ops))
    assert sum(got.values()) == pytest.approx(busy) == pytest.approx(13.0)


def test_scope_seconds_keeps_the_sum_at_the_union_when_children_overrun():
    ops = [("while", LOOP, 0.0, 4.0), ("gather", LOOKUP, 3.0, 5.0),
           ("late", None, 6.0, 7.0)]
    got = P.scope_seconds(ops)
    assert got == pytest.approx({LOOP: 3.0, LOOKUP: 2.0, P.UNSCOPED: 1.0})
    assert sum(got.values()) == pytest.approx(6.0)  # the union of the three


def test_gaps_are_labelled_by_the_innermost_program_span():
    idle = [(0.0, 1.0), (5.0, 5.2), (8.0, 8.05)]
    spans = [
        ("input_wait", 0.0, 1.0),     # dispatch thread: the whole gap
        ("input_stage", 0.0, 0.6),    # worker thread: its first part
        ("input_h2d", 0.6, 0.95),     # ... and its second
        ("eval_dispatch", 1.0, 1.1),
        ("eval_pass_outer", 0.0, 9.0),  # covers the gap too, but is longer
        ("serve_pad_stage", 5.05, 5.15),
    ]
    first, second, third = P.label_gaps(idle, spans, n=3)
    assert first["label"] == "input_wait" and first["seconds"] == pytest.approx(1.0)
    assert first["spans"] == pytest.approx({
        "input_wait": 1.0, "input_stage": 0.6, "input_h2d": 0.35, "eval_pass_outer": 1.0,
    })
    assert second["label"] == "eval_pass_outer"  # longest overlap of the 0.2 s
    assert second["spans"]["serve_pad_stage"] == pytest.approx(0.1)
    assert third["seconds"] == pytest.approx(0.05)
    assert P.label_gaps([(0.0, 1.0)], [], n=1)[0]["label"] == "(no span)"


def test_reduce_device_trace_window_covers_spans_and_ops():
    ops = [("conv", FNET, 1.0, 2.0), ("while", LOOP, 2.5, 4.0), ("g", LOOKUP, 2.5, 3.5)]
    spans = [("input_wait", 0.2, 1.0), ("eval_pull", 3.9, 4.1)]
    r = P.reduce_device_trace(ops, spans)
    assert r["window_s"] == pytest.approx(3.9) and r["busy_s"] == pytest.approx(2.5)
    assert r["scope_sum_s"] == pytest.approx(r["busy_s"])
    assert list(r["scope_s"]) == [FNET, LOOKUP, LOOP] or r["scope_s"][FNET] == 1.0
    start_up = r["idle_gaps"][0]
    assert start_up["label"] == "input_wait" and start_up["start_s"] == pytest.approx(0.0)
    assert start_up["seconds"] == pytest.approx(0.8)
    with pytest.raises(ValueError):
        P.reduce_device_trace([], spans)


def test_cost_ledger_banks_scopes_at_compile_time_and_merges_by_module():
    def fn(x):
        with jax.named_scope("raft.fnet"):
            y = jnp.sin(x) @ x
        with jax.named_scope("raft.cnet"):
            return jnp.cos(y)

    ledger = CostLedger(enabled=True)
    ledger.record_compiled("a", jax.jit(fn).lower(jnp.ones((8, 8))).compile())
    bank = ledger.op_scopes()
    assert set(bank) == {"jit_fn"}
    assert {"raft.fnet", "raft.cnet"} == set(bank["jit_fn"].values())
    snap = ledger.snapshot()
    assert "op_scopes" not in snap["entries"]["a"]  # kept out of --report
    assert snap["entries"]["a"]["module"] == "jit_fn"
    json.dumps(snap)
    # a second program under the same module name: what it places in
    # another scope is dropped, what agrees (or is new) stays
    some = next(iter(bank["jit_fn"]))
    ledger._entries["b"] = {
        "module": "jit_fn", "op_scopes": {some: "raft.upsample", "new.1": "raft.cnet"},
    }
    merged = ledger.op_scopes()["jit_fn"]
    assert some not in merged and merged["new.1"] == "raft.cnet"
    assert len(merged) == len(bank["jit_fn"])


def test_gap_label_goes_by_a_names_total_overlap():
    """Two batches' ``input_wait`` of 0.24 s each outweigh one 0.36 s
    ``eval_throttle_wait`` (the start-up gap of the eval cell, PR 24)."""
    spans = [("input_wait", 0.0, 0.24), ("input_wait", 0.25, 0.49),
             ("eval_throttle_wait", 0.49, 29.0), ("eval_dispatch", 0.24, 0.25)]
    (gap,) = P.label_gaps([(0.0, 0.85)], spans, n=1)
    assert gap["label"] == "input_wait"
    assert gap["spans"]["input_wait"] == pytest.approx(0.48)
    assert gap["spans"]["eval_throttle_wait"] == pytest.approx(0.36)
