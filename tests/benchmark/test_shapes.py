"""The shape functions against counts worked out by hand for the 2.7B recipe's widths
(32 q / 8 kv heads of 80, width 2560, SwiGLU 7680, vocabulary 50,304, sequence 4096)."""

import importlib.util
import json
from pathlib import Path

import pytest
import yaml

from benchmark.weights import DecoderShape

REPO = Path(__file__).resolve().parents[2]


def shape_function(name):
    spec = importlib.util.spec_from_file_location(name, REPO / "benchmark" / "shapes" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.count


def recipe(n_layer: int) -> DecoderShape:
    raw = yaml.safe_load((REPO / "benchmark/configs/modalities-2p7b-d6/train.yaml").read_text())
    return DecoderShape.from_model_config({**raw["model_raw"]["config"], "n_layer": n_layer})


ONE_CHIP = {"sequence_length": 4096, "rows_per_chip": 2, "q_heads_per_chip": 32, "kv_heads_per_chip": 8,
            "ce_rows_per_chip": 8192, "vocab_per_chip": 50304}


def test_parameter_counts_of_the_recipe():
    # per layer: q 2560x2560, k and v 2560x640 each, c_proj 2560x2560, three of 2560x7680
    per_layer = 2560 * 2560 * 2 + 2560 * 640 * 2 + 3 * 2560 * 7680
    assert per_layer == 75_366_400
    assert recipe(6).ffn_hidden == 7680 and recipe(6).head_dim == 80
    assert recipe(6).matmul_params() == 6 * per_layer + 2560 * 50304 == 580_976_640
    assert recipe(6).all_params() == 709_788_160, "the count PR 21's `run` printed at depth 6"
    assert recipe(32).all_params() == 2_669_447_680, "2.67 B at the published depth"


def test_operations_per_token():
    required = shape_function("dense_decoder_required_ops")(recipe(6), ONE_CHIP)["ops_per_token"]
    assert required == 6 * 580_976_640 + 6 * 6 * 4096 * 2560 == 3_863_347_200  # 3.86e9, ISSUE 23
    reference = shape_function("dense_decoder_reference_formula")(recipe(6), ONE_CHIP)["ops_per_token"]
    assert reference == 6 * 709_788_160 + 12 * 6 * 4096 * 2560 == 5_013_703_680  # 5.01e9
    # at 24,000 tokens/s on one v5e (197e12): 47.1% and 61.1%
    assert 100 * required * 24000 / 197e12 == pytest.approx(47.07, abs=0.01)
    assert 100 * reference * 24000 / 197e12 == pytest.approx(61.08, abs=0.01)


def test_flash_attention_counts():
    per_call = shape_function("flash_attention")(recipe(6), ONE_CHIP)
    u = 2 * 32 * 4096 * 4096 * 80  # one causal [S, S] matmul over 2 rows x 32 heads: 2 B H S S D / 2
    assert u == 85_899_345_920
    assert per_call["flash_attention_fwd"]["ops"] == 2 * u
    assert sum(k["ops"] for k in per_call.values()) == 6 * u, "2 forward + 4 backward matmuls a layer"
    q, kv = 2 * 2 * 32 * 4096 * 80, 2 * 2 * 8 * 4096 * 80
    assert per_call["flash_attention_fwd"]["bytes"] == 2 * q + 2 * kv == 104_857_600
    # compute bound by far: 0.87 ms of matmul against 0.13 ms of memory traffic on a v5e
    assert 2 * u / 197e12 == pytest.approx(0.872e-3, rel=1e-3) and (2 * q + 2 * kv) / 819e9 < 0.2e-3
    sharded = shape_function("flash_attention")(recipe(32), {**ONE_CHIP, "rows_per_chip": 1, "q_heads_per_chip": 16, "kv_heads_per_chip": 4})
    assert sharded["flash_attention_fwd"]["ops"] == 2 * u / 4, "dp 2 x tp 2: a quarter of the rows x heads a chip"


def test_fused_cross_entropy_counts():
    per_call = shape_function("fused_ce")(recipe(6), ONE_CHIP)
    matmul = 2 * 8192 * 2560 * 50304
    assert matmul == 2_109_902_684_160  # 10.7 ms at the v5e's bf16 peak
    assert {k: v["ops"] for k, v in per_call.items()} == {
        "fused_ce_fwd": matmul, "fused_ce_bwd_dh": matmul, "fused_ce_bwd_dw": matmul}
    assert per_call["fused_ce_bwd_dw"]["bytes"] == 2 * 8192 * 2560 + 2 * 2560 * 50304 + 4 * 2560 * 50304


def test_peaks_table_is_sourced_and_keyed_by_device_kind():
    from benchmark.device import peaks

    table = json.loads((REPO / "benchmark" / "peaks.json").read_text())
    assert "Google Cloud" in table["_source"]
    assert peaks("TPU v5 lite", REPO) == {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(SystemExit, match="no peaks"):
        peaks("TPU v9", REPO)
