"""The latent-attention / expert-layer decoder's counts against counts worked out by hand for
kanana-2-30b-a3b-instruct-2601's widths (2048; 32 heads of 128 + 64 for q and k and 128 for
v, a latent of 512; dense SwiGLU 6144; 128 experts of 768, 6 a token, 2 shared; untied head),
as ISSUE 30's table has them. A share of a peak over 100% fails a run in the harness, so the
two counts such a share rests on are checked here number by number."""

import dataclasses
import importlib.util
from pathlib import Path

import pytest
import yaml

from benchmark.weights_moe import MoEMLAShape

REPO = Path(__file__).resolve().parents[2]


def shape_function(name):
    spec = importlib.util.spec_from_file_location(name, REPO / "benchmark" / "shapes" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.count


def cell_shape() -> MoEMLAShape:
    return MoEMLAShape.from_yaml(yaml.safe_load((REPO / "benchmark/configs/kanana2-30b-a3b-d9/train.yaml").read_text()))


ONE_CHIP = {"sequence_length": 8192, "rows_per_chip": 2, "q_heads_per_chip": 32, "kv_heads_per_chip": 32,
            "ce_rows_per_chip": 16384, "vocab_per_chip": 16128, "pairs_held_per_token": 0.75}


def test_parameter_counts_are_issue_30s_table():
    s = cell_shape()
    attention = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    assert attention == 12_582_912 + 1_179_648 + 4_194_304 + 8_388_608 == 26_345_472 == s.attention_params()
    assert attention + 512 == 26_345_984, "with the latent's norm"
    shared, expert = 3 * 2048 * 1536, 3 * 2048 * 768
    assert (shared, expert, 128 * expert) == (9_437_184, 4_718_592, 603_979_776) and s.expert_params() == expert
    outside = attention + 512 + shared + (2048 * 128 + 128 + 4096)
    assert outside == 36_049_536 and s.layer_params("moe") == outside + 16 * expert == 111_547_008
    assert s.layer_params("mlp") == attention + 512 + 3 * 2048 * 6144 + 4096 == 64_098_816
    assert s.all_params() == 64_098_816 + 8 * 111_547_008 + (2 * 16128 * 2048 + 2048) == 1_022_537_216
    whole = dataclasses.replace(s, n_layer=48, experts_held=128, vocab_size=128256)
    assert whole.all_params() == 64_098_816 + 47 * (outside + 603_979_776) + 525_338_624 == 30_670_815_104, "the published model"


def test_operations_per_token_count_the_routed_experts_by_the_pairs_held():
    s = cell_shape()
    dense_layer = 26_345_472 + 3 * 2048 * 6144
    expert_layer = 26_345_472 + 2048 * 128 + 9_437_184 + 0.75 * 4_718_592
    assert s.layer_matmul_params_passed("mlp", 0.75) == dense_layer == 64_094_208
    assert s.layer_matmul_params_passed("moe", 0.75) == expert_layer == 39_583_744
    scores = 3 * 32 * (192 + 128) * 8192 * 9
    required = shape_function("moe_mla_required_ops")(s, ONE_CHIP)["ops_per_token"]
    assert required == 6 * (dense_layer + 8 * expert_layer + 2048 * 16128) + scores == pytest.approx(4.7477e9, rel=1e-4)
    # the causal scores are more than the projections in every layer: 251.7 M a token a layer against 6 x 26.3 M
    assert scores / 9 == 251_658_240 > 6 * 26_345_472
    # a token that brings no pair to a held expert costs the layer's other parts alone; all six, six experts
    none, six = (shape_function("moe_mla_required_ops")(s, {**ONE_CHIP, "pairs_held_per_token": p})["ops_per_token"] for p in (0.0, 6.0))
    assert six - none == 6 * 8 * 6 * 4_718_592
    # at 14,000 tokens/s on one v5e (197e12): 33.7% of the matmul peak
    assert 100 * required * 14000 / 197e12 == pytest.approx(33.74, abs=0.01)


def test_the_flash_kernels_at_two_head_sizes_by_hand():
    flash = shape_function("flash_attention_mla")(cell_shape(), ONE_CHIP)
    u192, u128 = 2 * 32 * 8192 * 8192 * 192, 2 * 32 * 8192 * 8192 * 128
    assert flash["flash_attention_fwd"]["ops"] == u192 + u128, "Q K^T at 192, P V at 128"
    assert flash["flash_attention_bwd_dq"]["ops"] == u192 + 0.5 * u128, "dQ at 192, half of dP at 128"
    assert flash["flash_attention_bwd_dkv"]["ops"] == u192 + 1.5 * u128, "dK at 192; dV and the other half of dP at 128"
    assert sum(k["ops"] for k in flash.values()) == 3 * (u192 + u128), "forward 1, backward 2"
    b192, b128 = 2 * 2 * 32 * 8192 * 192, 2 * 2 * 32 * 8192 * 128
    assert flash["flash_attention_fwd"]["bytes"] == 2 * b192 + 2 * b128
    assert flash["flash_attention_bwd_dkv"]["bytes"] == 3 * b192 + 4 * b128
    # equal widths give the accepted function's counts
    equal = dataclasses.replace(cell_shape(), qk_nope_head_dim=64, qk_rope_head_dim=64)
    one, two = shape_function("flash_attention_mla")(equal, ONE_CHIP), shape_function("flash_attention")(
        type("S", (), {"head_dim": 128})(), ONE_CHIP)
    assert one == two


def test_the_fused_ce_function_holds_for_this_shape():
    ce = shape_function("fused_ce")(cell_shape(), ONE_CHIP)
    assert {k: v["ops"] for k, v in ce.items()} == dict.fromkeys(("fused_ce_fwd", "fused_ce_bwd_dh", "fused_ce_bwd_dw"), 2.0 * 16384 * 2048 * 16128)
