"""The vocabulary of scope names (telemetry/scopes.py) on the compiled train step:
every operation falls into a pass and a component of the benchmark's rules file, the
scopes are metadata and nothing else, and `perfscope.scope_table` reads them back."""

import contextlib
import json
import os
import re
from pathlib import Path

import numpy as np
import pytest
import yaml

from modalities_tpu.telemetry import scopes
from modalities_tpu.telemetry.perfscope import analyze_hlo_text, format_perfscope_table, scope_table

REPO = Path(__file__).resolve().parents[2]
CELL = "train-2p7b-4k"
LISTS = ("pass", "component")
UNATTRIBUTED = "unattributed"


def compile_toy_train_step(tmp: Path) -> str:
    """The benchmark's train cell at toy size (tests/benchmark/toy.py), built as its
    mode builds it, lowered as `program_memory` lowers it: the optimized HLO text."""
    from benchmark.manifest import load_cell
    from benchmark.weights import DecoderShape
    from tests.benchmark.toy import make_toy_root

    root = make_toy_root(tmp)
    cell = load_cell(CELL, root)
    mode = cell.module("modes", cell.mode)
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = DecoderShape.from_model_config(raw["model_raw"]["config"])
    profile = raw["settings"]["step_profile"]
    scratch = root / ".bench_scratch" / cell.name
    (scratch / "data").mkdir(parents=True)
    cell.module("traffic", cell.traffic["generator"]).generate(
        cell.traffic, 1, scratch / "data" / "train.pbin", vocab_size=shape.vocab_size,
        sequence_length=int(profile["sequence_length"]))
    started_in = os.getcwd()
    try:
        _, fns = mode.build_program(cell, 1, scratch, shape)
    finally:
        os.chdir(started_in)
    keys = raw["settings"]["referencing_keys"]
    tokens = np.zeros((int(profile["local_train_micro_batch_size"]), int(profile["sequence_length"])), np.int32)
    host = {"samples": {keys["sample_key"]: tokens[None]}, "targets": {keys["target_key"]: tokens[None]}}
    batch = fns.put_batch(host, has_acc_dim=True)
    text = fns.lower_train_step(batch).compile().as_text()
    assert fns.scope_table(batch) == scope_table(text), "StepFunctions.scope_table is the table of its own compiled step"
    return text


@pytest.fixture(scope="module")
def hlo(tmp_path_factory) -> str:
    return compile_toy_train_step(tmp_path_factory.mktemp("scoped"))


@pytest.fixture(scope="module")
def rules() -> dict:
    raw = json.loads((REPO / "benchmark" / "scopes" / "train_dense.json").read_text())
    return {name: [(re.compile(pattern), bucket) for pattern, bucket in raw[name]] for name in LISTS}


def bucket_of(path: str, rows) -> str:
    return next(bucket for pattern, bucket in rows if pattern.search(path))


def every_op_name(hlo_text: str) -> set[str]:
    """Every `op_name` of the module, the insides of fusions among them, but for the
    bodies of reducers (`to_apply=` of a reduce, a scatter, a sort: never operations of
    their own, and named by a bare primitive or by whatever XLA last saw)."""
    reducers = set(re.findall(r"(?<!call\()to_apply=%?([\w.\-]+)", hlo_text)) - set(
        re.findall(r" call\(.*to_apply=%?([\w.\-]+)", hlo_text))
    names, skipping = set(), False
    for line in hlo_text.splitlines():
        header = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+(?:\(.*\)\s*->.*\{|\{)\s*$", line)
        if header:
            skipping = header.group(1) in reducers
        elif not skipping:
            names.update(re.findall(r'op_name="((?:[^"\\]|\\.)*)"', line))
    return names


# ------------------------------------------------------------------ (a) closure


@pytest.mark.parametrize("which", LISTS)
def test_every_operation_of_the_toy_train_step_falls_into_a_bucket(hlo, rules, which):
    table = scope_table(hlo)
    assert len(table) > 100
    left = {path for path in set(table.values()) | every_op_name(hlo) if bucket_of(path, rules[which]) == UNATTRIBUTED}
    assert not left, f"no rule of the list {which!r} takes {sorted(left)[:5]}"


@pytest.mark.parametrize("bare", [r"^jit\(train_step\)/[^/]+$", r"(^|/)jvp\(\)/", r"(^|/)transpose\(jvp\(\)\)/",
                                   r"^jit\(train_step\)/while/body/[^/]+$"])
def test_nothing_is_left_under_a_bare_wrapper(hlo, bare):
    """What autodiff does not mark has a scope of the vocabulary; the head has a name."""
    assert not [path for path in every_op_name(hlo) if re.search(bare, path)]


@pytest.mark.parametrize("scope", [s for s in scopes.UPDATE_SCOPES + scopes.MODEL_SCOPES + (scopes.HEAD_LOSS,)
                                   if s != scopes.ANOMALY_SELECT])
def test_each_scope_of_the_vocabulary_is_on_the_step(hlo, scope):
    """(`anomaly_select` is on the step only under an anomaly policy, which the cell does not set.)"""
    assert any(re.search(rf"[/(]{scope}[/)]", path) for path in every_op_name(hlo)), scope


def test_the_head_reads_as_a_pass_of_head_loss_and_the_blocks_as_their_modules(hlo):
    names = every_op_name(hlo)
    assert any("/jvp(head_loss)/" in n for n in names) and any("/transpose(jvp(head_loss))/" in n for n in names)
    for module in ("attn/q_attn", "attn/k_attn", "attn/v_attn", "attn/attn._project_out/c_proj", "mlp/W", "mlp/V", "mlp/W_2"):
        assert any(f"transpose(jvp(GPT2Module))/layer_carry/while/body/closed_call/blocks/block/{module}/" in n for n in names), module


# ------------------------------------------------------------------ (a') closure, for a stack of two kinds of layer


def compile_toy_hybrid_train_step(tmp: Path) -> str:
    """The benchmark's hybrid cell at toy size (tests/benchmark/toy_hybrid.py: state-space
    layers round one attention layer, every block rematerialized), built as its mode
    builds it: the optimized HLO text of its train step."""
    from benchmark.manifest import load_cell
    from benchmark.weights_hybrid import HybridShape
    from tests.benchmark.toy_hybrid import CELL as HYBRID_CELL, make_toy_hybrid_root

    root = make_toy_hybrid_root(tmp)
    cell = load_cell(HYBRID_CELL, root)
    mode = cell.module("modes", cell.mode)
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = HybridShape.from_yaml(raw)
    profile = raw["settings"]["step_profile"]
    scratch = root / ".bench_scratch" / cell.name
    (scratch / "data").mkdir(parents=True)
    cell.module("traffic", cell.traffic["generator"]).generate(
        cell.traffic, 1, scratch / "data" / "train.pbin", vocab_size=shape.vocab_size,
        sequence_length=int(profile["sequence_length"]))
    started_in = os.getcwd()
    try:
        _, fns = mode.build_program(cell, 1, scratch, shape)
    finally:
        os.chdir(started_in)
    keys = raw["settings"]["referencing_keys"]
    tokens = np.zeros((int(profile["local_train_micro_batch_size"]), int(profile["sequence_length"])), np.int32)
    host = {"samples": {keys["sample_key"]: tokens[None]}, "targets": {keys["target_key"]: tokens[None]}}
    return fns.lower_train_step(fns.put_batch(host, has_acc_dim=True)).compile().as_text()


@pytest.fixture(scope="module")
def hybrid_hlo(tmp_path_factory) -> str:
    return compile_toy_hybrid_train_step(tmp_path_factory.mktemp("scoped_hybrid"))


@pytest.fixture(scope="module")
def hybrid_rules() -> dict:
    raw = json.loads((REPO / "benchmark" / "scopes" / "train_hybrid.json").read_text())
    return {name: [(re.compile(pattern), bucket) for pattern, bucket in raw[name]] for name in LISTS}


@pytest.mark.parametrize("which", LISTS)
def test_every_operation_of_the_toy_hybrid_step_falls_into_a_bucket(hybrid_hlo, hybrid_rules, which):
    table = scope_table(hybrid_hlo)
    assert len(table) > 100
    paths = set(table.values()) | every_op_name(hybrid_hlo)
    left = {path for path in paths if bucket_of(path, hybrid_rules[which]) == UNATTRIBUTED}
    assert not left, f"no rule of the list {which!r} takes {sorted(left)[:5]}"
    if which == "component":
        found = {bucket_of(path, hybrid_rules[which]) for path in paths}
        assert {"ssm_scan", "ssm", "attn", "mlp", "norms", "residual", "head_loss", "wte", "layer_carry"} <= found


@pytest.mark.parametrize("scope", scopes.SSM_SCOPES)
def test_each_scope_of_the_mixer_is_on_the_hybrid_step_under_ssm(hybrid_hlo, scope):
    assert any(re.search(rf"/{scopes.SSM}/{scope}/", path) for path in every_op_name(hybrid_hlo)), scope


def test_the_runs_name_themselves_and_the_scan_holds_the_recurrence_alone(hybrid_hlo, hybrid_rules):
    names = every_op_name(hybrid_hlo)
    for run in ("run_0", "run_1", "run_2"):
        assert any(f"jvp(GPT2Module)/{run}/layer_carry/while/body/closed_call/blocks/block/" in n for n in names), run
    assert any("/run_1/layer_carry/while/body/closed_call/blocks/block/attn/" in n for n in names)
    # rematerialized: the backward pass reaches a block through `blocks/blocks/checkpoint/`, which the rules know
    backward = [n for n in names if "transpose(jvp(GPT2Module))" in n and "/ssm/scan/" in n]
    assert backward and all("blocks/blocks/checkpoint/" in n for n in backward)
    assert {bucket_of(n, hybrid_rules["component"]) for n in backward} == {"ssm_scan"}
    in_scan = {n.rsplit("/", 1)[-1] for n in names if "/ssm/scan/" in n}
    assert not in_scan & {"dot_general", "softplus", "logistic", "conv_general_dilated"}, "projections, softplus and gate are outside ssm/scan"


def test_the_scan_kernels_keep_their_names_under_ssm_scan(tmp_path, monkeypatch, hybrid_rules):
    """What a TPU traces: the dispatcher takes the Pallas kernels (here interpreted, so that
    their bodies are ordinary operations with a path). `selective_scan_fwd` runs in the
    forward pass and again where a block is rematerialized, `selective_scan_bwd` in the
    backward pass, both under `ssm/scan` and in no other scope, so the rules that read
    the plain form's time read the kernels' without an edit."""
    monkeypatch.setattr("modalities_tpu.ops.selective_scan.uses_kernels", lambda interpret=False: True)
    names = every_op_name(compile_toy_hybrid_train_step(tmp_path))
    of_kernels = [n for n in names if "selective_scan_" in n]
    assert any("/jvp(GPT2Module)/" in n and "/ssm/scan/selective_scan_fwd/" in n for n in of_kernels)
    assert any("transpose(jvp(GPT2Module))" in n and "rematted_computation" in n and "/ssm/scan/selective_scan_fwd/" in n for n in of_kernels)
    assert any("transpose(jvp(GPT2Module))" in n and "/ssm/scan/selective_scan_bwd/" in n for n in of_kernels)
    assert all("/ssm/scan/selective_scan_" in n for n in of_kernels)
    assert {bucket_of(n, hybrid_rules["component"]) for n in of_kernels} == {"ssm_scan"}
    assert {bucket_of(n, hybrid_rules["pass"]) for n in of_kernels} == {"forward", "backward"}
    assert re.search(r"`selective_scan_\{fwd,bwd\}`", scopes.__doc__), "the vocabulary names the kernels beside the other three families"


# ------------------------------------------------------------------ (a'') closure, for latent attention and expert layers


def compile_toy_moe_train_step(tmp: Path) -> str:
    """The benchmark's expert cell at toy size (tests/benchmark/toy_moe.py: a dense layer and two
    expert layers, latent attention in all three, every block rematerialized), built as its
    mode builds it: the optimized HLO text of its train step."""
    from benchmark.manifest import load_cell
    from benchmark.weights_moe import MoEMLAShape
    from tests.benchmark.toy_moe import CELL as MOE_CELL, make_toy_moe_root

    root = make_toy_moe_root(tmp)
    cell = load_cell(MOE_CELL, root)
    mode = cell.module("modes", cell.mode)
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = MoEMLAShape.from_yaml(raw)
    profile = raw["settings"]["step_profile"]
    scratch = root / ".bench_scratch" / cell.name
    (scratch / "data").mkdir(parents=True)
    cell.module("traffic", cell.traffic["generator"]).generate(
        cell.traffic, 1, scratch / "data" / "train.pbin", vocab_size=shape.vocab_size,
        sequence_length=int(profile["sequence_length"]))
    started_in = os.getcwd()
    try:
        _, fns = mode.build_program(cell, 1, scratch, shape)
    finally:
        os.chdir(started_in)
    keys = raw["settings"]["referencing_keys"]
    tokens = np.zeros((int(profile["local_train_micro_batch_size"]), int(profile["sequence_length"])), np.int32)
    host = {"samples": {keys["sample_key"]: tokens[None]}, "targets": {keys["target_key"]: tokens[None]}}
    return fns.lower_train_step(fns.put_batch(host, has_acc_dim=True)).compile().as_text()


@pytest.fixture(scope="module")
def moe_hlo(tmp_path_factory) -> str:
    return compile_toy_moe_train_step(tmp_path_factory.mktemp("scoped_moe"))


@pytest.fixture(scope="module")
def moe_rules() -> dict:
    raw = json.loads((REPO / "benchmark" / "scopes" / "train_moe.json").read_text())
    return {name: [(re.compile(pattern), bucket) for pattern, bucket in raw[name]] for name in LISTS}


@pytest.mark.parametrize("which", LISTS)
def test_every_operation_of_the_toy_moe_step_falls_into_a_bucket(moe_hlo, moe_rules, which):
    table = scope_table(moe_hlo)
    assert len(table) > 100
    paths = set(table.values()) | every_op_name(moe_hlo)
    left = {path for path in paths if bucket_of(path, moe_rules[which]) == UNATTRIBUTED}
    assert not left, f"no rule of the list {which!r} takes {sorted(left)[:5]}"
    if which == "component":
        found = {bucket_of(path, moe_rules[which]) for path in paths}
        assert {"attn", "moe_router", "moe_dispatch", "moe_experts", "moe_shared", "moe_combine", "mlp", "norms", "residual",
                "head_loss", "wte", "layer_carry"} <= found


@pytest.mark.parametrize("scope", scopes.MOE_SCOPES)
def test_each_scope_of_the_expert_layer_is_on_the_step_under_moe_in_both_passes(moe_hlo, scope):
    names = [n for n in every_op_name(moe_hlo) if re.search(rf"/{scopes.MOE}/(.*/)?{scope}/", n)]
    assert any("/jvp(GPT2Module)/" in n for n in names), scope
    assert any("transpose(jvp(GPT2Module))" in n for n in names), scope


def test_the_runs_are_by_feed_forward_and_latent_attention_keeps_the_names_under_attn(moe_hlo, moe_rules):
    names = every_op_name(moe_hlo)
    assert any("jvp(GPT2Module)/run_0/layer_carry/while/body/closed_call/blocks/block/mlp/" in n for n in names)
    assert any("jvp(GPT2Module)/run_1/layer_carry/while/body/closed_call/blocks/block/moe/" in n for n in names)
    assert not any("/run_0/" in n and "/moe/" in n for n in names) and not any("/run_1/" in n and "/block/mlp/" in n for n in names)
    for module in ("q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "c_proj", "rope", "attn_core"):
        assert any(f"/blocks/block/attn/{module}/" in n for n in names), module
    # the grouped products are dots under moe/experts and nowhere else in the layer's loops; gathers and adds by token are not
    in_experts = {n.rsplit("/", 1)[-1] for n in names if re.search(r"/moe/(.*/)?experts/", n)}
    in_dispatch = {n.rsplit("/", 1)[-1] for n in names if re.search(r"/moe/(.*/)?(dispatch|combine)/", n)}
    assert "dot_general" in in_experts and "dot_general" not in in_dispatch
    assert re.search(r"MOE_DISPATCH\s+dispatch", scopes.__doc__) and re.search(r"MOE_COMBINE\s+combine", scopes.__doc__)


# ------------------------------------------------------------------ (a3) closure, for a stack walked several times


def compile_toy_looped_train_step(tmp: Path) -> str:
    """The benchmark's looped cell at toy size (tests/benchmark/toy_looped.py: 3 layers walked 4 times, sandwich
    norms, the exit gate and the loss over the exits, every block rematerialized), built as its mode builds it:
    the optimized HLO text of its train step."""
    from benchmark.manifest import load_cell
    from benchmark.weights_looped import LoopedShape
    from tests.benchmark.toy_looped import CELL as LOOPED_CELL, make_toy_looped_root

    root = make_toy_looped_root(tmp)
    cell = load_cell(LOOPED_CELL, root)
    mode = cell.module("modes", cell.mode)
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = LoopedShape.from_yaml(raw)
    profile = raw["settings"]["step_profile"]
    scratch = root / ".bench_scratch" / cell.name
    (scratch / "data").mkdir(parents=True)
    cell.module("traffic", cell.traffic["generator"]).generate(
        cell.traffic, 1, scratch / "data" / "train.pbin", vocab_size=shape.vocab_size,
        sequence_length=int(profile["sequence_length"]))
    started_in = os.getcwd()
    try:
        _, fns = mode.build_program(cell, 1, scratch, shape)
    finally:
        os.chdir(started_in)
    keys = raw["settings"]["referencing_keys"]
    tokens = np.zeros((int(profile["local_train_micro_batch_size"]), int(profile["sequence_length"])), np.int32)
    host = {"samples": {keys["sample_key"]: tokens[None]}, "targets": {keys["target_key"]: tokens[None]}}
    return fns.lower_train_step(fns.put_batch(host, has_acc_dim=True)).compile().as_text()


@pytest.fixture(scope="module")
def looped_hlo(tmp_path_factory) -> str:
    return compile_toy_looped_train_step(tmp_path_factory.mktemp("scoped_looped"))


@pytest.fixture(scope="module")
def looped_rules() -> dict:
    raw = json.loads((REPO / "benchmark" / "scopes" / "train_looped.json").read_text())
    return {name: [(re.compile(pattern), bucket) for pattern, bucket in raw[name]] for name in LISTS}


@pytest.mark.parametrize("which", LISTS)
def test_every_operation_of_the_toy_looped_step_falls_into_a_bucket(looped_hlo, looped_rules, which):
    table = scope_table(looped_hlo)
    assert len(table) > 100
    paths = set(table.values()) | every_op_name(looped_hlo)
    left = {path for path in paths if bucket_of(path, looped_rules[which]) == UNATTRIBUTED}
    assert not left, f"no rule of the list {which!r} takes {sorted(left)[:5]}"
    if which == "component":
        found = {bucket_of(path, looped_rules[which]) for path in paths}
        assert {"attn", "mlp", "norms", "residual", "head_loss", "exit_gate", "wte", "layer_carry", "loop_carry"} <= found
        assert "model_other" not in found, sorted(p for p in paths if bucket_of(p, looped_rules[which]) == "model_other")[:5]


@pytest.mark.parametrize("scope", scopes.LOOP_SCOPES + (scopes.POST_ATTENTION_NORM, scopes.POST_FFN_NORM))
def test_each_scope_of_the_loop_is_on_the_step_in_both_passes(looped_hlo, scope):
    names = [n for n in every_op_name(looped_hlo) if re.search(rf"[/(]{scope}[/)]", n)]
    assert any("jvp(" in n and "transpose(" not in n for n in names), scope
    assert any("transpose(jvp(" in n for n in names), scope


def test_the_walks_hold_the_layer_scan_the_final_norm_and_the_gate_and_the_exit_loss_sits_in_head_loss(looped_hlo, looped_rules):
    names = every_op_name(looped_hlo)
    walk = "GPT2Module._walks/loop/while/body/closed_call/"  # Flax names the method; under full remat the walks are `_walks_in_place`'s own loops
    inside, back = "/jvp(GPT2Module)/" + walk, "/transpose(jvp(GPT2Module))/" + walk
    assert any(inside + "layer_carry/while/body/closed_call/blocks/block/mlp/W/" in n for n in names)
    # the hand-written backward recomputes a block from its kept input and pulls the cotangent through it: both under the backward's walk
    assert any(back + "layer_carry/while/body/closed_call/jvp(rematted_computation)/blocks/block/mlp/W/" in n for n in names)
    assert any(back + "layer_carry/while/body/closed_call/transpose(jvp(rematted_computation))/blocks/block/mlp/W/" in n for n in names)
    assert any(back + "transpose(jvp(rematted_computation))/lm_head_norm/" in n for n in names)
    assert any(inside + "lm_head_norm/" in n for n in names) and any(inside + "exit_gate/" in n for n in names)
    assert any("/jvp(head_loss)/exit_loss/" in n for n in names) and any("transpose(jvp(head_loss))/exit_loss/" in n for n in names)
    # what no walk changes (rotary tables, the causal mask) is hoisted out of the loop; a block's kernels are used inside it alone
    assert not any(re.search(r"/blocks/.*/(attn/[qkv]_attn|mlp/(W|V|W_2))/", n) and "/loop/" not in n for n in names)
    # the final norm inside the loop is a norm, not the head's; the gate is its own bucket
    component = looped_rules["component"]
    assert bucket_of(inside + "lm_head_norm/mul", component) == "norms" and bucket_of(inside + "exit_gate/dot_general", component) == "exit_gate"
    assert bucket_of(back + "layer_carry/while/body/closed_call/transpose(jvp(rematted_computation))/blocks/block/post_ffn_norm/mul", component) == "norms"
    assert bucket_of(inside + "layer_carry/while/body/closed_call/blocks/blocks/checkpoint/block/post_ffn_norm/mul", component) == "norms"  # autodiff's form: no remat, `selective_op`
    assert re.search(r"LOOP\s+loop", scopes.__doc__) and re.search(r"EXIT_LOSS\s+exit_loss", scopes.__doc__)


# ------------------------------------------------------------------ (b) only metadata


class _NoScope(contextlib.ContextDecorator):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def without_metadata(hlo_text: str) -> str:
    """The module's text without `metadata={...}` and without the tables of file names,
    function names, locations and stack frames that the metadata points into."""
    lines = [line for line in hlo_text.splitlines() if line not in TABLES and not re.match(r"^\d+ ", line)]
    return re.sub(r",? ?metadata=\{[^}]*\}", "", "\n".join(lines))


def test_scopes_are_only_metadata(hlo, tmp_path, monkeypatch):
    """With `jax.named_scope` a null context (Flax's module names go with it), the
    optimized HLO is the same but for `metadata={...}`."""
    import jax

    monkeypatch.setattr(jax, "named_scope", lambda name: _NoScope())
    bare = compile_toy_train_step(tmp_path)
    assert "grad_accumulate" not in bare and "grad_accumulate" in hlo
    assert without_metadata(bare) == without_metadata(hlo)


# ------------------------------------------------------------------ (c) scope_table on a hand-written module

HAND_WRITTEN = '''HloModule jit_step, is_scheduled=true

%add_reducer (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8] parameter(0)
  %multiply.1 = f32[8,8] multiply(%p0, %p0), metadata={op_name="jit(step)/jvp(GPT2Module)/blocks/block/mlp/mul"}
  ROOT %tanh.1 = f32[8,8] tanh(%multiply.1), metadata={op_name="jit(step)/jvp(GPT2Module)/blocks/block/mlp/tanh"}
}

%fused_computation.2 (p0: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %p0 = f32[8,8] parameter(0)
  %negate.2 = f32[8,8] negate(%p0), metadata={op_name="jit(step)/optimizer/neg"}
  ROOT %tuple.2 = (f32[8,8], f32[8,8]) tuple(%negate.2, %p0)
}

%body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8,8] get-tuple-element(%arg), index=1
  %fusion.1 = f32[8,8] fusion(%x), kind=kLoop, calls=%fused_computation.1
  %flash_attention_fwd.3 = f32[8,8] custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(GPT2Module)/blocks/block/attn/attn_core/flash_attention_fwd/pallas_call" source_file="a.py" source_line=3}
  %reduce.4 = f32[] reduce(%flash_attention_fwd.3, %i), dimensions={0,1}, to_apply=%add_reducer, metadata={op_name="jit(step)/grad_norm/reduce_sum"}
  %copy-start.5 = (f32[8,8], f32[8,8], u32[]) copy-start(%x)
  ROOT %tuple.6 = (s32[], f32[8,8]) tuple(%i, %flash_attention_fwd.3)
}

%cond (arg: (s32[], f32[8,8])) -> pred[] {
  %arg = (s32[], f32[8,8]) parameter(0)
  ROOT %lt.7 = pred[] compare(%arg, %arg), direction=LT, metadata={op_name="jit(step)/grad_accumulate/while/cond/lt"}
}

ENTRY %main (state: f32[8,8], /*index=1*/n: s32[]) -> f32[8,8] {
  %state = f32[8,8] parameter(0), metadata={op_name="state.params[\\'w\\']"}
  %n = s32[] parameter(1)
  %tuple.8 = (s32[], f32[8,8]) tuple(%n, %state)
  %while.10 = (s32[], f32[8,8]) while(%tuple.8), condition=%cond, body=%body, metadata={op_name="jit(step)/grad_accumulate/while"}
  %y = f32[8,8] get-tuple-element(%while.10), index=1
  %fusion.2 = (f32[8,8], f32[8,8]) fusion(%y), kind=kLoop, calls=%fused_computation.2
  %copy.11 = f32[8,8] copy(%state), metadata={op_name="state.params[\\'w\\']"}
  ROOT %out = f32[8,8] get-tuple-element(%fusion.2), index=0
}
'''


def test_scope_table_of_a_hand_written_module():
    assert scope_table(HAND_WRITTEN) == {
        "fusion.1": "jit(step)/jvp(GPT2Module)/blocks/block/mlp/tanh",  # a fusion without metadata carries its root's
        "flash_attention_fwd.3": "jit(step)/jvp(GPT2Module)/blocks/block/attn/attn_core/flash_attention_fwd/pallas_call",
        "reduce.4": "jit(step)/grad_norm/reduce_sum",  # and its reducer's body is no operation of its own
        "lt.7": "jit(step)/grad_accumulate/while/cond/lt",
        "while.10": "jit(step)/grad_accumulate/while",
        "fusion.2": "jit(step)/optimizer/neg",  # the root is a tuple without a name: the last named instruction inside
        "copy.11": "state.params['w']",  # XLA's copy of an argument keeps the argument's name
    }  # no parameter, tuple or get-tuple-element, nothing from inside a fusion, and not the unnamed copy-start


@pytest.mark.parametrize("op_name, path", [
    ("jit(train_step)/grad_accumulate/while/body/closed_call/transpose(jvp(GPT2Module))/layer_carry/while/body/closed_call/"
     "blocks/block/mlp/W/dot_general", "grad_accumulate/transpose(jvp(GPT2Module))/layer_carry/blocks/block/mlp/W"),
    ("jit(train_step)/optimizer/clip/mul", "optimizer/clip"),
    ("jit(train_step)/grad_accumulate/while/body/closed_call/jvp(head_loss)/fused_ce_fwd/while/body/cond/branch_1_fun/add",
     "grad_accumulate/jvp(head_loss)/fused_ce_fwd"),
    ("jit(train_step)/add", "(no scope)"), ("reduce_sum", "(no scope)"), (None, "(no scope)"),
])
def test_scope_path_keeps_the_scopes_and_drops_the_plumbing(op_name, path):
    assert scopes.scope_path(op_name) == path


def test_the_operators_table_has_a_column_by_scope_that_closes_on_the_total(hlo):
    report = analyze_hlo_text(hlo)
    for key in ("ops", "flops", "bytes"):
        assert sum(row[key] for row in report["by_scope"].values()) == report["total"][key], key
    assert sum(row["est_time_s"] for row in report["by_scope"].values()) == pytest.approx(report["total"]["est_time_s"], rel=1e-6)
    assert any(scope.startswith("optimizer") for scope in report["by_scope"])
    page = format_perfscope_table(report)
    assert "scope (telemetry/scopes.py)" in page and "more scopes)" in page
