"""The collective plan (telemetry/collective_plan.py, the rows `perfscope.analyze_hlo_text` keeps): a collective's
mesh axis from the geometry of its replica groups, the rows of a real compile on four CPU devices against the
`collective:*` buckets of the same report, the chip's wrapped forms on a hand-written module, and the preflight that
records a plan on a mesh of several devices and does nothing at all on one."""

import pytest
import yaml

from modalities_tpu.telemetry import Telemetry, collective_plan, spans
from modalities_tpu.telemetry.perfscope import _collective_axis, analyze_hlo_text, perfscope_from_compiled
from tests.benchmark.toy import REPO, TOY_SEQ, _shrink_model

MESH = {"dp_shard": 2, "tp": 2}  # partition id = dp_shard * 2 + tp: the mesh's own axis order


# ------------------------------------------------------------------ the axis, by geometry


@pytest.mark.parametrize("groups, axis", [
    ("replica_groups={{0,1},{2,3}}", "tp"),
    ("replica_groups={{0,2},{1,3}}", "dp_shard"),
    ("replica_groups={{0,1,2,3}}", "dp_shard+tp"),
    ("replica_groups=[2,2]<=[4]", "tp"),
    ("replica_groups=[2,2]<=[2,2]T(1,0)", "dp_shard"),
    ("replica_groups=[1,4]<=[4]", "dp_shard+tp"),
    ("replica_groups=[1,4]<=[2,2]T(1,0)", "dp_shard+tp"),
    ("source_target_pairs={{0,1},{1,0},{2,3},{3,2}}", "tp"),
    ("source_target_pairs={{0,2},{2,0},{1,3},{3,1}}", "dp_shard"),
])
def test_two_axes_of_one_size_are_told_apart_by_where_the_groups_lie(groups, axis):
    line = f"%c = f32[16] all-reduce(%a), channel_id=3, {groups}, use_global_device_ids=true, to_apply=%add"
    assert _collective_axis(line, MESH) == axis
    # the mesh's own axis order decides, not the alphabet: with tp outermost the same sets read the other way round
    swapped = {"tp": "dp_shard", "dp_shard": "tp"}.get(axis, "tp+dp_shard" if "+" in axis else axis)
    assert _collective_axis(line, {"tp": 2, "dp_shard": 2}) == swapped


def test_axes_of_size_one_are_never_named_and_size_matching_is_only_the_fall_back():
    line = "%c = f32[16] all-gather(%a), replica_groups={{0,2},{1,3}}, dimensions={0}"
    assert _collective_axis(line, {"dp_replicate": 1, "dp_shard": 2, "cp": 1, "tp": 2}) == "dp_shard"
    assert _collective_axis(line, None) == "size2"  # no mesh: no geometry
    assert _collective_axis(line, {"dp_shard": 2}) == "dp_shard"  # partition 3 is not in a mesh of two: matched by size
    # groups that are not the whole extent of the axes they differ along carry no axis of their own: by size, as before
    assert _collective_axis("%c = f32[4] all-reduce(%a), replica_groups={{0,1},{2,3}}", {"dp_shard": 4, "tp": 2}) == "tp"
    assert _collective_axis("%c = f32[4] all-reduce(%a), replica_groups={{0,1,2}}", {"dp_shard": 4, "tp": 2}) == "size3"
    assert _collective_axis("%c = f32[4] all-reduce(%a), replica_groups={}", MESH) == "all"


def test_a_group_that_crosses_slices_is_dcn_whatever_else_it_spans():
    sizes = {"dcn": 2, "dp_shard": 2, "tp": 2}
    assert _collective_axis("%c = f32[4] all-reduce(%a), replica_groups={{0,4},{1,5},{2,6},{3,7}}", sizes) == "dcn"
    assert _collective_axis("%c = f32[4] all-reduce(%a), replica_groups={{0,1,2,3,4,5,6,7}}", sizes) == "dcn"
    assert _collective_axis("%c = f32[4] all-reduce(%a), replica_groups={{0,5}}", sizes) == "dcn"  # not a whole extent: still dcn
    assert _collective_axis("%c = f32[4] all-reduce(%a), replica_groups={{0,1},{2,3},{4,5},{6,7}}", sizes) == "tp"
    assert _collective_axis("%c = f32[4] all-reduce(%a), replica_groups={{0,2},{1,3},{4,6},{5,7}}", sizes) == "dp_shard"


# ------------------------------------------------------------------ the rows, on the forms a chip's module holds

CHIP_FORMS = """
HloModule jit_train_step, entry_computation_layout={(f32[8,16])->f32[8,16]}

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %sum = f32[] add(%x, %y)
}

%all-reduce-scatter.3 (input: bf16[8,32]) -> bf16[8,16] {
  %input = bf16[8,32]{1,0} parameter(0)
  %all-reduce.9 = bf16[8,32]{1,0} all-reduce(%input), channel_id=7, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add
  %zero = u32[] constant(0)
  ROOT %dynamic-slice.1 = bf16[8,16]{1,0} dynamic-slice(%all-reduce.9, %zero, %zero), dynamic_slice_sizes={8,16}
}

%fused_start (p: bf16[4,16]) -> (bf16[4,16], bf16[8,16]) {
  %p = bf16[4,16]{1,0} parameter(0)
  %all-gather.1 = bf16[8,16]{1,0} all-gather(%p), channel_id=9, replica_groups=[2,2]<=[4], dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(train_step)/jvp(GPT2Module)/layer_carry/while/body/closed_call/blocks/block/mlp/W/dot_general"}
  ROOT %custom-call.1 = (bf16[4,16]{1,0}, bf16[8,16]{1,0}) custom-call(%all-gather.1), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.5 (p: bf16[4,16], q: bf16[8,16]) -> bf16[8,16] {
  %p = bf16[4,16]{1,0} parameter(0)
  %q = bf16[8,16]{1,0} parameter(1)
  %all-gather.2 = bf16[8,16]{1,0} all-gather(%p), channel_id=9, replica_groups=[2,2]<=[4], dimensions={0}, use_global_device_ids=true
  ROOT %mul = bf16[8,16]{1,0} multiply(%q, %q)
}

%fused_done (p: bf16[4,16], q: bf16[8,16]) -> bf16[8,16] {
  %p = bf16[4,16]{1,0} parameter(0)
  %q = bf16[8,16]{1,0} parameter(1)
  %all-gather.3 = bf16[8,16]{1,0} all-gather(%p), channel_id=9, replica_groups=[2,2]<=[4], dimensions={0}, use_global_device_ids=true
  ROOT %custom-call.2 = bf16[8,16]{1,0} custom-call(%p, %q, %all-gather.3), custom_call_target="AsyncCollectiveDone"
}

%wrapped_reduce_scatter (p: f32[8,16]) -> f32[4,16] {
  %p = f32[8,16]{1,0} parameter(0)
  ROOT %reduce-scatter.2 = f32[4,16]{1,0} reduce-scatter(%p), channel_id=11, replica_groups={{0,1},{2,3}}, dimensions={0}, to_apply=%add
}

%body (carry: (s32[], bf16[4,16], bf16[8,32])) -> (s32[], bf16[4,16], bf16[8,32]) {
  %carry = (s32[], bf16[4,16]{1,0}, bf16[8,32]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %w = bf16[4,16]{1,0} get-tuple-element(%carry), index=1
  %g = bf16[8,32]{1,0} get-tuple-element(%carry), index=2
  %async-collective-start.4 = (bf16[4,16]{1,0}, bf16[8,16]{1,0}) fusion(%w), kind=kCustom, calls=%fused_start
  %started.w = bf16[4,16]{1,0} get-tuple-element(%async-collective-start.4), index=0
  %started.out = bf16[8,16]{1,0} get-tuple-element(%async-collective-start.4), index=1
  %fusion.21 = bf16[8,16]{1,0} fusion(%started.w, %started.out), kind=kOutput, calls=%async_collective_fusion.5
  %async-collective-done.4 = bf16[8,16]{1,0} fusion(%started.w, %fusion.21), kind=kCustom, calls=%fused_done
  %fusion.30 = bf16[8,16]{1,0} fusion(%g), kind=kCustom, calls=%all-reduce-scatter.3, metadata={op_name="jit(train_step)/transpose(jvp(GPT2Module))/layer_carry/while/body/closed_call/blocks/block/attn/c_proj/dot_general"}
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  ROOT %out = (s32[], bf16[4,16]{1,0}, bf16[8,32]{1,0}) tuple(%next, %w, %g)
}

%cond (carry: (s32[], bf16[4,16], bf16[8,32])) -> pred[] {
  %carry = (s32[], bf16[4,16]{1,0}, bf16[8,32]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %layers = s32[] constant(6)
  ROOT %lt = pred[] compare(%i, %layers), direction=LT
}

ENTRY %main (a: f32[8,16], w: bf16[4,16], g: bf16[8,32]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %w = bf16[4,16]{1,0} parameter(1)
  %g = bf16[8,32]{1,0} parameter(2)
  %zero = s32[] constant(0)
  %init = (s32[], bf16[4,16]{1,0}, bf16[8,32]{1,0}) tuple(%zero, %w, %g)
  %while.1 = (s32[], bf16[4,16]{1,0}, bf16[8,32]{1,0}) while(%init), condition=%cond, body=%body
  %all-gather-start.7 = (f32[8,16]{1,0}, f32[16,16]{1,0}) all-gather-start(%a), channel_id=2, replica_groups=[1,4]<=[4], dimensions={0}, metadata={op_name="jit(train_step)/jvp(head_loss)/transpose"}
  %all-gather-done.7 = f32[16,16]{1,0} all-gather-done(%all-gather-start.7)
  %reduce-scatter-start.1 = ((f32[8,16]{1,0}), f32[4,16]{1,0}) async-start(%a), calls=%wrapped_reduce_scatter
  %reduce-scatter-done.1 = f32[4,16]{1,0} async-done(%reduce-scatter-start.1)
  ROOT %psum.5 = f32[8,16]{1,0} all-reduce(%a), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(train_step)/optimizer/clip/reduce_sum"}
}
"""


def test_a_wrapped_collective_is_one_row_under_the_name_the_trace_prints():
    plan = collective_plan.plan_from_hlo_text(CHIP_FORMS, MESH)
    rows = {row["name"]: row for row in plan["rows"]}
    assert plan["module"] == "jit_train_step"
    assert set(rows) == {"async-collective-start.4", "fusion.30", "all-gather-start.7", "reduce-scatter-start.1", "psum.5"}
    gather = rows["async-collective-start.4"]  # three fusions, three copies of the instruction on one channel: one collective
    assert (gather["kind"], gather["axis"], gather["done"], gather["steps"]) == ("all-gather", "tp", "async-collective-done.4", ["fusion.21"])
    assert gather["bytes"] == 8 * 16 * 2 and gather["times"] == 6  # its own output once; the loop's trip count off its condition
    assert gather["scope"] == "jvp(GPT2Module)/layer_carry/blocks/block/mlp/W"
    scatter = rows["fusion.30"]  # an all-reduce and its slice in one fusion: the chip's reduce-scatter, the scope off the wrapper
    assert (scatter["kind"], scatter["axis"], scatter["done"], scatter["times"]) == ("reduce-scatter", "dp_shard", None, 6)
    assert scatter["scope"] == "transpose(jvp(GPT2Module))/layer_carry/blocks/block/attn/c_proj"
    assert (rows["all-gather-start.7"]["done"], rows["all-gather-start.7"]["axis"], rows["all-gather-start.7"]["times"]) == ("all-gather-done.7", "dp_shard+tp", 1)
    assert (rows["reduce-scatter-start.1"]["kind"], rows["reduce-scatter-start.1"]["done"], rows["reduce-scatter-start.1"]["axis"]) == (
        "reduce-scatter", "reduce-scatter-done.1", "tp")
    assert (rows["psum.5"]["kind"], rows["psum.5"]["scope"]) == ("all-reduce", "optimizer/clip")
    # the rows are the buckets' instructions: bytes agree, bucket by bucket
    report = analyze_hlo_text(CHIP_FORMS, MESH)
    for axis in ("tp", "dp_shard", "dp_shard+tp"):
        assert report["buckets"][f"collective:{axis}"]["bytes"] == sum(r["bytes"] for r in plan["rows"] if r["axis"] == axis)
        assert report["buckets"][f"collective:{axis}"]["ops"] == sum(1 for r in plan["rows"] if r["axis"] == axis)
    assert plan["totals"]["tp|all-gather"] == {"count": 1, "bytes": 256, "count_a_run": 6, "bytes_a_run": 6 * 256}
    assert plan["bytes_a_run"] == sum(r["bytes"] * r["times"] for r in plan["rows"])


# what a `shard_map`'s collectives look like in a chip's module (PR 51): every one on channel 1, two of them cut into a start and
# a done fusion in ONE loop body and told apart by the compiler's `chain_id`, and a bare `reduce-scatter` whose emitter's notes
# print the operand's shape a second time
SHARD_MAP_FORMS = """
HloModule jit_train_step, entry_computation_layout={(bf16[1,1,8,16])->bf16[1,1,8,16]}

%add (x: bf16[], y: bf16[]) -> bf16[] {
  %x = bf16[] parameter(0)
  %y = bf16[] parameter(1)
  ROOT %sum = bf16[] add(%x, %y)
}

%start_mlp (p: bf16[1,1,8,16]) -> (bf16[1,1,8,16], bf16[2,1,8,16]) {
  %p = bf16[1,1,8,16]{3,2,1,0} parameter(0)
  %all-gather.135 = bf16[2,1,8,16]{3,2,1,0} all-gather(%p), channel_id=1, replica_groups={{0,1},{2,3}}, dimensions={0}, use_global_device_ids=true, frontend_attributes={chain_id="0"}, metadata={op_name="jit(train_step)/transpose(jvp(GPT2Module))/layer_carry/while/body/closed_call/blocks/block/mlp/checkpoint/rematted_computation/mlp.seq_gathered/shard_map/all_gather"}
  ROOT %custom-call.1 = (bf16[1,1,8,16]{3,2,1,0}, bf16[2,1,8,16]{3,2,1,0}) custom-call(%all-gather.135), custom_call_target="AsyncCollectiveStart"
}

%done_mlp (p: bf16[1,1,8,16], q: bf16[2,1,8,16]) -> bf16[2,1,8,16] {
  %p = bf16[1,1,8,16]{3,2,1,0} parameter(0)
  %q = bf16[2,1,8,16]{3,2,1,0} parameter(1)
  %all-gather.139 = bf16[2,1,8,16]{3,2,1,0} all-gather(%p), channel_id=1, replica_groups={{0,1},{2,3}}, dimensions={0}, use_global_device_ids=true, frontend_attributes={chain_id="0"}
  ROOT %custom-call.2 = bf16[2,1,8,16]{3,2,1,0} custom-call(%p, %q, %all-gather.139), custom_call_target="AsyncCollectiveDone"
}

%start_attn (p: bf16[1,1,8,16]) -> (bf16[1,1,8,16], bf16[2,1,8,16]) {
  %p = bf16[1,1,8,16]{3,2,1,0} parameter(0)
  %all-gather.163 = bf16[2,1,8,16]{3,2,1,0} all-gather(%p), channel_id=1, replica_groups={{0,1},{2,3}}, dimensions={0}, use_global_device_ids=true, frontend_attributes={chain_id="4"}, metadata={op_name="jit(train_step)/transpose(jvp(GPT2Module))/layer_carry/while/body/closed_call/blocks/block/attn/checkpoint/rematted_computation/attn.seq_gathered/shard_map/all_gather"}
  ROOT %custom-call.3 = (bf16[1,1,8,16]{3,2,1,0}, bf16[2,1,8,16]{3,2,1,0}) custom-call(%all-gather.163), custom_call_target="AsyncCollectiveStart"
}

%done_attn (p: bf16[1,1,8,16], q: bf16[2,1,8,16]) -> bf16[2,1,8,16] {
  %p = bf16[1,1,8,16]{3,2,1,0} parameter(0)
  %q = bf16[2,1,8,16]{3,2,1,0} parameter(1)
  %all-gather.167 = bf16[2,1,8,16]{3,2,1,0} all-gather(%p), channel_id=1, replica_groups={{0,1},{2,3}}, dimensions={0}, use_global_device_ids=true, frontend_attributes={chain_id="4"}
  ROOT %custom-call.4 = bf16[2,1,8,16]{3,2,1,0} custom-call(%p, %q, %all-gather.167), custom_call_target="AsyncCollectiveDone"
}

%body (carry: (s32[], bf16[1,1,8,16])) -> (s32[], bf16[1,1,8,16]) {
  %carry = (s32[], bf16[1,1,8,16]{3,2,1,0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %h = bf16[1,1,8,16]{3,2,1,0} get-tuple-element(%carry), index=1
  %async-collective-start = (bf16[1,1,8,16]{3,2,1,0}, bf16[2,1,8,16]{3,2,1,0}) fusion(%h), kind=kCustom, calls=%start_mlp
  %a.0 = bf16[1,1,8,16]{3,2,1,0} get-tuple-element(%async-collective-start), index=0
  %a.1 = bf16[2,1,8,16]{3,2,1,0} get-tuple-element(%async-collective-start), index=1
  %async-collective-start.4 = (bf16[1,1,8,16]{3,2,1,0}, bf16[2,1,8,16]{3,2,1,0}) fusion(%h), kind=kCustom, calls=%start_attn
  %b.0 = bf16[1,1,8,16]{3,2,1,0} get-tuple-element(%async-collective-start.4), index=0
  %b.1 = bf16[2,1,8,16]{3,2,1,0} get-tuple-element(%async-collective-start.4), index=1
  %async-collective-done = bf16[2,1,8,16]{3,2,1,0} fusion(%a.0, %a.1), kind=kCustom, calls=%done_mlp
  %async-collective-done.4 = bf16[2,1,8,16]{3,2,1,0} fusion(%b.0, %b.1), kind=kCustom, calls=%done_attn
  %reduce_scatter.66 = bf16[1,1,8,16]{3,2,1,0} reduce-scatter(%async-collective-done.4), channel_id=1, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, dimensions={0}, to_apply=%add, metadata={op_name="jit(train_step)/jvp(GPT2Module)/layer_carry/while/body/closed_call/blocks/block/attn/attn._project_out/c_proj/shard_map/reduce_scatter"}, backend_config={"collective_algorithm_config":{"emitter":"SingleInputAllReduceScatterFusion","debug":"Type: 1D; original_shape: bf16[2,1,8,16]{3,2,1,0:T(8,128)(2,1)}; sharding_dim: 0"}}
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  ROOT %out = (s32[], bf16[1,1,8,16]{3,2,1,0}) tuple(%next, %reduce_scatter.66)
}

%cond (carry: (s32[], bf16[1,1,8,16])) -> pred[] {
  %carry = (s32[], bf16[1,1,8,16]{3,2,1,0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %layers = s32[] constant(3)
  ROOT %lt = pred[] compare(%i, %layers), direction=LT
}

ENTRY %main (h: bf16[1,1,8,16]) -> bf16[1,1,8,16] {
  %h = bf16[1,1,8,16]{3,2,1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], bf16[1,1,8,16]{3,2,1,0}) tuple(%zero, %h)
  %while.1 = (s32[], bf16[1,1,8,16]{3,2,1,0}) while(%init), condition=%cond, body=%body
  ROOT %result = bf16[1,1,8,16]{3,2,1,0} get-tuple-element(%while.1), index=1
}
"""


def test_two_cut_collectives_on_one_channel_are_two_rows_and_a_reduce_scatter_counts_its_operand():
    plan = collective_plan.plan_from_hlo_text(SHARD_MAP_FORMS, MESH)
    rows = {row["name"]: row for row in plan["rows"]}
    assert set(rows) == {"async-collective-start", "async-collective-start.4", "reduce_scatter.66"}
    assert (rows["async-collective-start"]["done"], rows["async-collective-start"]["steps"]) == ("async-collective-done", [])
    assert (rows["async-collective-start.4"]["done"], rows["async-collective-start.4"]["steps"]) == ("async-collective-done.4", [])
    assert rows["async-collective-start"]["scope"].endswith("blocks/block/mlp/rematted_computation/mlp.seq_gathered/shard_map")
    assert rows["async-collective-start.4"]["scope"].endswith("blocks/block/attn/rematted_computation/attn.seq_gathered/shard_map")
    scatter = rows["reduce_scatter.66"]  # what a chip puts in, [2, 1, 8, 16] bfloat16, as a fused one counts: not the output, not the notes' shape too
    assert (scatter["kind"], scatter["axis"], scatter["bytes"], scatter["times"]) == ("reduce-scatter", "tp", 2 * 8 * 16 * 2, 3)
    assert plan["totals"]["tp|all-gather"] == {"count": 2, "bytes": 2 * 512, "count_a_run": 6, "bytes_a_run": 6 * 512}
    assert plan["totals"]["tp|reduce-scatter"] == {"count": 1, "bytes": 512, "count_a_run": 3, "bytes_a_run": 3 * 512}


def test_a_reduce_scatter_is_counted_one_way_fused_or_bare():
    rows = {row["name"]: row for row in collective_plan.plan_from_hlo_text(CHIP_FORMS, MESH)["rows"]}
    assert rows["fusion.30"]["bytes"] == 8 * 32 * 2  # the all-reduce inside the chip's fusion has the operand's shape
    assert rows["reduce-scatter-start.1"]["bytes"] == 8 * 16 * 4  # a bare one: its output [4, 16] float32 times the two of its group


# ------------------------------------------------------------------ a real compile, on four devices and on one


def _toy_yaml(tmp_path, dp_shard: int, tp: int):
    raw = yaml.safe_load((REPO / "benchmark" / "configs" / "modalities-2p7b-d6" / "train.yaml").read_text())
    _shrink_model(raw["model_raw"]["config"])
    raw["model_raw"]["config"]["sequence_length"] = "${settings.step_profile.sequence_length}"
    raw["settings"]["step_profile"].update(sequence_length=TOY_SEQ, local_train_micro_batch_size=1)
    raw["settings"]["training_target"] = {"num_target_steps": 64, "num_target_tokens": 64 * dp_shard * TOY_SEQ}
    raw["device_mesh"]["config"].update(device_type="cpu", data_parallel_shard_degree=dp_shard, tensor_parallel_degree=tp,
                                        world_size=dp_shard * tp)
    path = tmp_path / f"toy_{dp_shard}x{tp}.yaml"
    path.write_text(yaml.safe_dump(raw, sort_keys=False))
    return path


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    from modalities_tpu.utils.recipe_validation import build_lowered_train_step

    tmp = tmp_path_factory.mktemp("plans")
    return {devices: build_lowered_train_step(_toy_yaml(tmp, *layout)) for devices, layout in ((4, (2, 2)), (1, (1, 1)))}


def _act_on_the_cpu(monkeypatch):
    """The preflight is a no-op where the backend states no limit: give it one, as its own tests do."""
    monkeypatch.setattr("modalities_tpu.trainer.min_bytes_limit", lambda: 2**40)
    monkeypatch.setattr("modalities_tpu.telemetry.memscope.min_bytes_limit", lambda: 2**40)


def test_a_step_on_a_two_by_two_mesh_has_rows_on_both_axes_that_add_up_to_the_buckets(built, monkeypatch, tmp_path):
    from modalities_tpu.telemetry import set_active_telemetry
    from modalities_tpu.trainer import Trainer

    four = built[4]
    _act_on_the_cpu(monkeypatch)
    telemetry = Telemetry(output_folder_path=tmp_path / "telemetry")
    collective_plan.PROCESS_PLANS.clear()
    previous = set_active_telemetry(telemetry)
    try:
        assert Trainer._preflight_memscope(four.fns, four.batch_abstract, telemetry) is not None
    finally:
        set_active_telemetry(previous)
    assert four.fns.preflight_compiled is None  # handed on once, then dropped
    (plan,) = collective_plan.PROCESS_PLANS
    assert plan["mesh_axes"] == MESH and plan["module"].startswith("jit_train_step")
    by_axis = {axis: [r for r in plan["rows"] if r["axis"] == axis] for axis in ("dp_shard", "tp")}
    for axis, rows in by_axis.items():
        assert rows, f"no collective on {axis}: {sorted({r['axis'] for r in plan['rows']})}"
        assert any("blocks/block/" in r["scope"] for r in rows), (axis, sorted({r["scope"] for r in rows}))
    # the same compile's report: the rows are what its buckets counted
    report = four.fns.perfscope_report(four.batch_abstract)
    buckets = {name[len("collective:"):]: b for name, b in report["buckets"].items() if name.startswith("collective:")}
    assert buckets["dp_shard"]["ops"] > 0 and buckets["tp"]["ops"] > 0
    assert {axis: b["bytes"] for axis, b in buckets.items()} == {
        axis: sum(r["bytes"] for r in plan["rows"] if r["axis"] == axis) for axis in {r["axis"] for r in plan["rows"]}}
    # the span, the sink's event and the gauges
    record = next(r for r in spans.PROCESS_LOG.records if r.name == "collective_plan")
    assert record.parent == "preflight_memscope"
    events = [line for line in telemetry.sink_path.read_text().splitlines() if '"collective_plan"' in line and '"resilience"' in line]
    assert len(events) == 1 and '"largest"' in events[0]
    snapshot = str(telemetry.metrics.snapshot())
    assert "train_collective_bytes" in snapshot and "train_collective_count" in snapshot
    telemetry.close()


def test_on_one_device_no_plan_is_recorded_and_no_span_opens(built, monkeypatch):
    from modalities_tpu.trainer import Trainer

    one = built[1]
    _act_on_the_cpu(monkeypatch)
    collective_plan.PROCESS_PLANS.clear()
    before = len(spans.PROCESS_LOG.records)
    walked = []
    monkeypatch.setattr(collective_plan, "plan_from_hlo_text", lambda *a, **k: walked.append(a))
    assert Trainer._preflight_memscope(one.fns, one.batch_abstract, Telemetry()) is not None
    opened = [r.name for r in list(spans.PROCESS_LOG.records)[before:]]
    assert "preflight_memscope" in opened and "collective_plan" not in opened
    assert not collective_plan.PROCESS_PLANS and not walked and one.fns.preflight_compiled is None


def test_perfscope_from_compiled_keeps_the_rows(built):
    report = perfscope_from_compiled(built[4].lowered.compile(), MESH)
    assert report["collectives"] and {"name", "done", "steps", "kind", "axis", "bytes", "times", "scope"} == set(report["collectives"][0])
