"""The cut's arithmetic of `zaya1-8b-ep2` (ISSUE 40's numbers, from the YAML as run), and the two shape functions the
cell's shares of a peak read: required operations by the positions a causal query may see, never by tiles."""

import dataclasses
import json
from pathlib import Path

import pytest
import yaml

from benchmark.manifest import load_module
from benchmark.weights_cca_moe import CcaMoEShape

REPO = Path(__file__).resolve().parents[2]
RAW = yaml.safe_load((REPO / "benchmark" / "configs" / "zaya1-8b-ep2" / "train.yaml").read_text())
SHAPE = CcaMoEShape.from_yaml(RAW)
RUN = {"sequence_length": 8192, "rows_per_chip": 2, "q_heads_per_chip": 8, "kv_heads_per_chip": 2, "ce_rows_per_chip": 16384,
       "vocab_per_chip": 32784, "pairs_held_per_token": 8 / 17}


def test_the_cut_is_the_issues_arithmetic():
    assert SHAPE.attention_params() == 5_242_880 == 2048 * (1024 + 256 + 128 + 128) + 1024 * 2048
    assert SHAPE.conv_params() == 332_800 == 1280 * 2 + 1280 + 10 * 128 * 128 * 2 + 1280 and SHAPE.grouped_conv_params() == 327_680
    assert SHAPE.router_params() == 661_009 == 2048 * 256 + 2 * 256 * 256 + 256 * 17 + 3 * 256 + 256 + 256 + 17
    assert SHAPE.expert_params() == 12_582_912 and 8 * SHAPE.expert_params() == 100_663_296
    assert SHAPE.layer_params() == 106_920_467 == 5_242_880 + 332_800 + 2 + 661_009 + 100_663_296 + 2 * 2048 + 8 * 2048
    assert 32_784 * 2048 == 67_141_632 and SHAPE.n_layer == 10 and SHAPE.all_params() == 1_136_348_350 == 10 * 106_920_467 + 67_141_632 + 2048
    assert dataclasses.replace(SHAPE, n_layer=8).all_params() == 922_507_416, "ISSUE 40's first depth: about 922.5 M"
    uncut = dataclasses.replace(SHAPE, n_layer=40, experts_held=16, vocab_size=262_272)
    assert uncut.layer_params() == 207_583_763 and uncut.all_params() == 8_840_485_624, "the published model: about 8.84 B"
    # what a token passes of the published model besides the table: attention with its convolutions, the router, one expert
    assert 40 * (5_242_880 + 332_800 + 661_009 + 12_582_912) == pytest.approx(0.75e9, rel=0.01)
    meta = json.loads((REPO / "benchmark" / "configs" / "zaya1-8b-ep2" / "meta.json").read_text())
    assert meta["parameters"].startswith("1,136,348,350") and "8,840,485,624" in meta["parameters"]


def test_required_operations_a_token_are_the_issues_shares():
    count = load_module(REPO, "shapes", "cca_moe_required_ops").count
    per_token = count(SHAPE, RUN)["ops_per_token"]
    layers = SHAPE.n_layer
    projections, convolution, router = 6 * layers * 5_242_880, 6 * layers * 327_680, 6 * layers * (2048 * 256 + 2 * 256 * 256 + 256 * 17)
    experts, head = 6 * layers * (8 / 17) * 12_582_912, 6 * 2048 * 32_784
    scores = 12 * 8 * 128 * layers * 8193 / 2
    assert per_token == pytest.approx(projections + convolution + router + experts + head + scores)
    # ISSUE 40 at 8 layers: scores 29% of the required forward operations, head 29%, held experts 20%, projections 18%, router and convolutions 3%
    eight = count(dataclasses.replace(SHAPE, n_layer=8), RUN)["ops_per_token"]
    assert eight / 3 == pytest.approx(463e6, rel=0.01)
    assert (scores * 8 / layers) / eight == pytest.approx(0.29, abs=0.01) and head / eight == pytest.approx(0.29, abs=0.01)
    assert (experts * 8 / layers) / eight == pytest.approx(0.205, abs=0.01) and (projections * 8 / layers) / eight == pytest.approx(0.18, abs=0.01)
    assert ((convolution + router) * 8 / layers) / eight == pytest.approx(0.033, abs=0.005)
    fewer = count(SHAPE, {**RUN, "pairs_held_per_token": 0.0})["ops_per_token"]
    assert per_token - fewer == pytest.approx(experts), "the routed work by the pairs held, as the counter read them: none where every token chose elsewhere"


def test_the_flash_kernels_are_counted_by_label_and_by_the_causal_triangle():
    count = load_module(REPO, "shapes", "flash_attention_cca").count
    calls = count(SHAPE, RUN)
    u = 2.0 * 2 * 8 * (8192 * 8193 // 2) * 128
    assert calls["flash_attention_fwd"]["ops"] == 2 * u and calls["flash_attention_bwd"]["ops"] == 4 * u
    assert calls["flash_attention_bwd_dq"]["ops"] + calls["flash_attention_bwd_dkv"]["ops"] == calls["flash_attention_bwd"]["ops"]
    assert calls["flash_attention_fwd"]["bytes"] == 2 * 2 * 2 * 8192 * 128 * (8 + 2) and calls["flash_attention_bwd"]["bytes"] == 2 * calls["flash_attention_fwd"]["bytes"]
    # never by tiles: at 1024 x 1024 a row of 8192 walks 36 tiles of 64 a head, 8 of them on the diagonal; the triangle is 0.5 + 1 / 16384 of the square
    assert (8192 * 8193 // 2) / (36 * 1024 * 1024) == pytest.approx(0.889, abs=0.001)
    spec = json.loads((REPO / "benchmark" / "metrics" / "flash_attention_cca_roofline.json").read_text())
    assert spec["pattern"] == "^flash_attention_(fwd|bwd|bwd_dq|bwd_dkv)$" and spec["shape_function"] == "flash_attention_cca"


def test_the_fused_cross_entropy_is_counted_by_the_rows_held_not_by_the_padded_block():
    calls = load_module(REPO, "shapes", "fused_ce").count(SHAPE, RUN)
    assert calls["fused_ce_fwd"]["ops"] == 2.0 * 16384 * 2048 * 32_784
