"""The arithmetic of the gated-delta-rule configuration (ISSUE 44, reckoned again): parameters by sub-layer as the chip holds
them and as published, the required operations a token by part, and the flash kernels' required work at 16:2 heads of 256."""

import dataclasses
import importlib.util
from pathlib import Path

import pytest
import yaml

from benchmark.weights_gdn_moe import GdnMoEShape

REPO = Path(__file__).resolve().parents[2]


def shape_function(name: str):
    spec = importlib.util.spec_from_file_location(name, REPO / "benchmark" / "shapes" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.count


@pytest.fixture(scope="module")
def shape():
    return GdnMoEShape.from_yaml(yaml.safe_load((REPO / "benchmark" / "configs" / "qwen3-next-80b-a3b-d4" / "train.yaml").read_text()))


def test_the_parameters_are_the_issues_by_sub_layer(shape):
    assert shape.gdn_params() == 2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * 2048 == 33_718_464
    assert shape.attention_params() == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256 == 27_263_488
    assert shape.expert_params() == 3_145_728 and shape.outside_experts_params() == 1_048_576 + 3_145_728 + 2_048 == 4_196_352
    assert shape.layer_params("gdn") == 239_245_504 and shape.layer_params("attn") == 232_790_528
    assert shape.all_params() == 3 * 239_245_504 + 232_790_528 + 77_793_280 == 1_028_320_320
    published = dataclasses.replace(shape, kinds=("gdn", "gdn", "gdn", "attn") * 12, experts_held=512, vocab_size=151936)
    assert published.all_params() == 79_674_391_296
    # what a token passes of the published model, with both tables: the name's "A3B"
    passed = 36 * (shape.gdn_params() + 4096) + 12 * (shape.attention_params() + 4096) + 48 * (shape.outside_experts_params() + 10 * shape.expert_params())
    assert abs(passed + 2 * 151936 * 2048 - 3.87e9) < 0.02e9


def test_the_required_operations_a_token_by_part(shape):
    count = shape_function("gdn_moe_required_ops")
    run = {"sequence_length": 16384, "pairs_held_per_token": 1.25}
    total = count(shape, run)["ops_per_token"]
    rule_mixers = 3 * (6 * (shape.gdn_matmul_params() + 4 * 8192) + 3 * shape.rule_forward_ops_per_token())
    scores = 12 * 16 * 256 * 16385 / 2
    attention_projections = 6 * shape.attention_matmul_params()
    experts = 4 * 6 * (shape.outside_experts_params() + 1.25 * shape.expert_params())
    head = 6 * 2048 * 18992
    assert total == pytest.approx(rule_mixers + scores + attention_projections + experts + head)
    # ISSUE 44's shares of the required FORWARD operations, about 551 M a token (a third of the step's): the three rule mixers 40%,
    # the attention's scores 24% and projections 10%, the four expert layers 12%, the head 14%
    assert total / 3 == pytest.approx(551e6, rel=0.02)
    shares = [part / total for part in (rule_mixers, scores, attention_projections, experts, head)]
    assert shares == pytest.approx([0.40, 0.24, 0.10, 0.12, 0.14], abs=0.015)
    assert shape.rule_forward_ops_per_token() == 5_242_880, "the rule itself 5.2 M a layer (ISSUE 44: about 6 M): small by count"
    more = count(shape, {**run, "pairs_held_per_token": 2.25})["ops_per_token"]
    assert more - total == pytest.approx(4 * 6 * shape.expert_params()), "a pair a token more, in each of the four layers"


def test_the_flash_kernels_required_work_at_heads_of_256(shape):
    count = shape_function("flash_attention_gdn")
    run = {"rows_per_chip": 1, "q_heads_per_chip": 16, "kv_heads_per_chip": 2, "sequence_length": 16384}
    kernels = count(shape, run)
    u = 2.0 * 16 * (16384 * 16385 // 2) * 256
    assert kernels["flash_attention_fwd"]["ops"] == 2 * u and kernels["flash_attention_bwd"]["ops"] == 4 * u
    assert kernels["flash_attention_bwd_dq"]["ops"] + kernels["flash_attention_bwd_dkv"]["ops"] == 4 * u
    q_bytes, kv_bytes = 2 * 16 * 16384 * 256, 2 * 2 * 16384 * 256
    assert kernels["flash_attention_fwd"]["bytes"] == 2 * q_bytes + 2 * kv_bytes and kernels["flash_attention_bwd"]["bytes"] == 4 * q_bytes + 4 * kv_bytes
