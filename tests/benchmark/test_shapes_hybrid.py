"""The hybrid decoder's counts against counts worked out by hand for AI21-Jamba2-3B's
widths (2560; Mamba-1 with d_inner 5120, state 16, rank 160, 4 taps; 20 q on 1 kv head of
128; SwiGLU 8192; tied table), as ISSUE 26's table has them."""

import dataclasses
import importlib.util
from pathlib import Path

import pytest
import yaml

from benchmark.weights_hybrid import HybridShape

REPO = Path(__file__).resolve().parents[2]


def shape_function(name):
    spec = importlib.util.spec_from_file_location(name, REPO / "benchmark" / "shapes" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.count


def cell_shape() -> HybridShape:
    return HybridShape.from_yaml(yaml.safe_load((REPO / "benchmark/configs/jamba2-3b-d14/train.yaml").read_text()))


def published(n_layer: int, vocab: int = 65536) -> HybridShape:
    kinds = tuple("attn" if i % 14 == 7 else "ssm" for i in range(n_layer))
    return dataclasses.replace(cell_shape(), kinds=kinds, vocab_size=vocab)


ONE_CHIP = {"sequence_length": 4096, "rows_per_chip": 1, "q_heads_per_chip": 20, "kv_heads_per_chip": 1,
            "ce_rows_per_chip": 4096, "vocab_per_chip": 32768}


def test_parameter_counts_of_the_published_widths():
    s = cell_shape()
    mixer = 2560 * 10240 + (4 * 5120 + 5120) + 5120 * 192 + (160 * 5120 + 5120) + 5120 * 16 + 5120 + 5120 * 2560 + 192
    assert mixer == 26_214_400 + 25_600 + 983_040 + 824_320 + 81_920 + 5_120 + 13_107_200 + 192 == 41_241_792
    swiglu = 3 * 2560 * 8192
    assert swiglu == 62_914_560 and s.layer_params("ssm") == mixer + swiglu + 2 * 2560 == 104_161_472
    attention = 2560 * 2560 * 2 + 2560 * 128 * 2
    assert attention == 13_762_560 and s.layer_params("attn") == attention + swiglu + 2 * 2560 == 76_682_240
    one_period = 13 * 104_161_472 + 76_682_240
    assert one_period == 1_430_781_376
    assert published(14).all_params() == one_period + 65536 * 2560 + 2560 == 1_598_556_096, "ISSUE 26's count at the whole table"
    assert s.all_params() == one_period + 32768 * 2560 + 2560 == 1_514_670_016, "what the cell runs: half the table"
    assert published(28).all_params() == 2 * one_period + 167_774_720 == 3_029_337_472, "the published 28 layers"
    # matmul parameters: every kernel (dt_proj among them), the table once as the head; no convolution, bias, A_log, D or norm
    assert s.layer_matmul_params("ssm") == 26_214_400 + 983_040 + 160 * 5120 + 13_107_200 + swiglu == 104_038_400
    assert s.matmul_params() == 13 * 104_038_400 + (attention + swiglu) + 32768 * 2560 == 1_513_062_400


def test_operations_per_token_count_attention_in_one_layer_of_fourteen():
    required = shape_function("hybrid_ssm_required_ops")(cell_shape(), ONE_CHIP)["ops_per_token"]
    assert required == 6 * 1_513_062_400 + 6 * 1 * 4096 * 2560 == 9_141_288_960
    # at 5,447 tokens/s on one v5e (197e12): 25.3% of the matmul peak
    assert 100 * required * 5447 / 197e12 == pytest.approx(25.28, abs=0.01)


def test_the_kernels_shape_functions_hold_for_this_shape():
    """20 query heads on 1 key/value head of 128, and the tied table's rows as the head's columns."""
    flash = shape_function("flash_attention")(cell_shape(), ONE_CHIP)
    u = 1 * 20 * 4096 * 4096 * 128
    assert flash["flash_attention_fwd"]["ops"] == 2 * u and sum(k["ops"] for k in flash.values()) == 6 * u
    q, kv = 2 * 20 * 4096 * 128, 2 * 1 * 4096 * 128
    assert flash["flash_attention_fwd"]["bytes"] == 2 * q + 2 * kv
    ce = shape_function("fused_ce")(cell_shape(), ONE_CHIP)
    assert {k: v["ops"] for k, v in ce.items()} == dict.fromkeys(("fused_ce_fwd", "fused_ce_bwd_dh", "fused_ce_bwd_dw"), 2.0 * 4096 * 2560 * 32768)
