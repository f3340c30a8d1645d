"""The looped decoder's counts against counts worked out by hand for Ouro-2.6B's widths (2048; 16 heads of 128;
SwiGLU 5632; vocabulary 49,152 untied; 4 walks), as ISSUE 32's section C has them: the parameters of the cut, the
bytes its memory rests on, and the required operations a token, which the share of the peak rests on (over 100%
fails a run in the harness). The program's own MFU calculator counts the same operations."""

import dataclasses
import importlib.util
from pathlib import Path

import pytest
import yaml

from benchmark.weights_looped import LoopedShape

REPO = Path(__file__).resolve().parents[2]
GIB = 1024**3


def shape_function(name):
    spec = importlib.util.spec_from_file_location(name, REPO / "benchmark" / "shapes" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.count


def cell_shape() -> LoopedShape:
    return LoopedShape.from_yaml(yaml.safe_load((REPO / "benchmark/configs/ouro-2p6b-t4/train.yaml").read_text()))


ONE_CHIP = {"sequence_length": 4096, "rows_per_chip": 1, "q_heads_per_chip": 16, "kv_heads_per_chip": 16,
            "ce_rows_per_chip": 4 * 4096, "vocab_per_chip": 49152}


def test_parameter_counts_are_issue_32s():
    s = cell_shape()
    attention, swiglu = 4 * 2048 * 2048, 3 * 2048 * 5632
    assert (attention, swiglu) == (16_777_216, 34_603_008) and s.layer_matmul_params() == attention + swiglu == 51_380_224
    assert s.layer_params() == 51_380_224 + 4 * 2048 == 51_388_416, "four norms a block"
    assert s.outer_params() == 2 * 100_663_296 + 2048 + 2049 == 201_330_689, "two tables, the final norm, the gate's vector and bias"
    assert s.all_params() == 16 * 51_388_416 + 201_330_689 == 1_023_545_345
    whole = dataclasses.replace(s, n_layer=48)
    assert whole.all_params() == 48 * 51_388_416 + 201_330_689 == 2_667_974_657, "the published model"
    for walks in (1, 2, 7):
        assert dataclasses.replace(s, total_ut_steps=walks).all_params() == s.all_params(), "one parameter tree whatever T is"
    assert (s.applications, dataclasses.replace(s, n_layer=48).applications) == (64, 192)


def test_the_bytes_the_cut_rests_on():
    """bf16 weights and both moments are 6 bytes a parameter, the bf16 gradient 2 (PERF.md section 4)."""
    s = cell_shape()
    state, gradient = 6 * s.all_params() / GIB, 2 * s.all_params() / GIB
    assert state == pytest.approx(5.72, abs=0.005) and gradient == pytest.approx(1.91, abs=0.005)
    stacked_layers = 2 * 16 * s.layer_params() / GIB  # the scanned run's parameters held a second time; the accumulator over the walks the same
    assert stacked_layers == pytest.approx(1.53, abs=0.005)
    block_inputs = s.applications * 1 * 4096 * 2048 * 2 / GIB  # 64 kept block inputs of [4096, 2048] bf16 at microbatch 1
    assert block_inputs == 1.0 and 2 * block_inputs == 2.0, "ISSUE 32 sized microbatch 2; its first lever, microbatch 1, was pulled (meta.json)"
    head_tables = 2 * 49152 * 2048 * 6 / GIB
    assert head_tables == pytest.approx(1.125) , "201 M parameters of the whole vocabulary at 6 bytes"


def test_operations_per_token_count_a_parameter_once_for_every_application():
    s = cell_shape()
    required = shape_function("looped_required_ops")(s, ONE_CHIP)["ops_per_token"]
    layers, attention, head = 6 * 51_380_224 * 16 * 4, 6 * 4 * 16 * 4096 * 2048, 6 * 4 * 2048 * 49152
    assert (layers, attention, head) == (19_730_006_016, 3_221_225_472, 2_415_919_104)
    assert required == layers + attention + head == 25_367_150_592, "ISSUE 32: 2.4 of 25.4 G a token are the four exits' head"
    assert shape_function("looped_required_ops")(dataclasses.replace(s, total_ut_steps=1), ONE_CHIP)["ops_per_token"] * 4 == required
    # one walk is the dense decoder's count but for the embedding's rows, which the dense formula of the program counts and this does not
    dense = shape_function("dense_decoder_required_ops")(
        type("S", (), {"n_layer": 16, "n_embd": 2048, "matmul_params": lambda self: 16 * 51_380_224 + 2048 * 49152})(), ONE_CHIP)["ops_per_token"]
    assert dense * 4 == required
    # at 3,000 tokens/s on one v5e (197e12): 38.6% of the matmul peak
    assert 100 * required * 3000 / 197e12 == pytest.approx(38.63, abs=0.01)


def test_the_programs_calculator_counts_the_same_operations():
    from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM, GPT2LLMConfig
    from modalities_tpu.utils.mfu import GPT2MFUCalculator
    from tests.models.test_looped import TOY

    model = GPT2LLM(**GPT2LLMConfig(**TOY).model_dump())
    calculator = GPT2MFUCalculator(n_layer=3, sequence_length=64, n_embd=128, world_size=1, wrapped_model=model)
    toy = LoopedShape.from_yaml({"model_raw": {"config": TOY}})
    required = shape_function("looped_required_ops")(toy, {"sequence_length": 64})["ops_per_token"]
    assert calculator.looped_flops_per_token == required == 6 * 4 * (3 * (4 * 128 * 128 + 3 * 128 * 256 + 64 * 128) + 128 * 512)
    assert calculator.compute(1000.0) == pytest.approx(1000.0 * required / 1e12), "against the nominal peak a CPU gets"
    dense = GPT2LLM(**GPT2LLMConfig(**{k: v for k, v in TOY.items() if k != "loop_config"}).model_dump())
    assert GPT2MFUCalculator(n_layer=3, sequence_length=64, n_embd=128, world_size=1, wrapped_model=dense).looped_flops_per_token is None


def test_the_accepted_kernel_functions_hold_for_this_shape():
    """Read the same way as in every cell (PERF.md section 7: both counts are stale there too): per call of a kernel."""
    ce = shape_function("fused_ce")(cell_shape(), ONE_CHIP)
    assert {k: v["ops"] for k, v in ce.items()} == dict.fromkeys(("fused_ce_fwd", "fused_ce_bwd_dh", "fused_ce_bwd_dw"), 2.0 * 16384 * 2048 * 49152)
    flash = shape_function("flash_attention")(cell_shape(), ONE_CHIP)
    assert flash["flash_attention_fwd"]["ops"] == 2.0 * 16 * 4096 * 4096 * 128
