"""The cut's arithmetic of `mellum2-12b-a2p5b-d12` (ISSUE 38's numbers, from the YAML as run), and the two shape
functions the cell's shares of a peak read: required operations by the positions a query may see, never by tiles."""

import dataclasses
import json
from pathlib import Path

import pytest
import yaml

from benchmark.manifest import load_module
from benchmark.weights_swa_moe import SwaMoEShape

REPO = Path(__file__).resolve().parents[2]
RAW = yaml.safe_load((REPO / "benchmark" / "configs" / "mellum2-12b-a2p5b-d12" / "train.yaml").read_text())
SHAPE = SwaMoEShape.from_yaml(RAW)
RUN = {"sequence_length": 16384, "rows_per_chip": 1, "q_heads_per_chip": 32, "kv_heads_per_chip": 4, "ce_rows_per_chip": 16384,
       "vocab_per_chip": 12288, "pairs_held_per_token": 1.0}


def test_the_cut_is_the_issues_arithmetic():
    assert SHAPE.attention_params() == 21_233_664 and SHAPE.router_params() == 147_456 and SHAPE.expert_params() == 6_193_152
    assert SHAPE.layer_params() == 70_930_944 == 21_233_664 + 147_456 + 8 * 6_193_152 + 4_608
    assert 2 * 12_288 * 2304 + 2304 == 56_625_408 and SHAPE.all_params() == 907_796_736
    uncut = dataclasses.replace(SHAPE, kinds=SHAPE.kinds[:4] * 7, experts_held=64, vocab_size=98_304)
    assert uncut.layer_params() == 417_747_456 and uncut.all_params() == 12_149_915_904
    # what a token passes of the published model: attention, the router, 8 of 64 experts, both tables' rows for it counted as the source counts them
    assert 28 * (21_233_664 + 147_456 + 8 * 6_193_152 + 4_608) + 2 * 98_304 * 2304 + 2304 == 2_439_053_568
    # ISSUE 38's levers, both whole periods
    assert dataclasses.replace(SHAPE, kinds=SHAPE.kinds[:8]).all_params() == 624_072_960
    assert dataclasses.replace(SHAPE, kinds=SHAPE.kinds[:4] * 4).all_params() == 1_191_520_512
    meta = json.loads((REPO / "benchmark" / "configs" / "mellum2-12b-a2p5b-d12" / "meta.json").read_text())
    assert meta["parameters"].startswith("907,796,736") and "12,149,915,904" in meta["parameters"]


def test_positions_seen_and_the_windows_band():
    window = load_module(REPO, "shapes", "flash_attention_window")
    assert window.entries(16384, 1024) == 16_253_440 and window.entries(16384, None) == 16384 * 16385 // 2
    assert window.entries(512, 1024) == window.entries(512, None), "a window wider than the row is the causal triangle"
    assert SHAPE.positions_seen("swa", 16384) == pytest.approx(16_253_440 / 16384) == pytest.approx(992.03125)
    assert SHAPE.positions_seen("attn", 16384) == 8192.5


def test_required_operations_a_token_are_the_issues_shares():
    count = load_module(REPO, "shapes", "swa_moe_required_ops").count
    per_token = count(SHAPE, RUN)["ops_per_token"]
    projections = 6 * 12 * (21_233_664 + 147_456)
    experts = 6 * 12 * 6_193_152
    head = 6 * 2304 * 12_288
    scores_global, scores_window = 3 * 12 * 32 * 128 * 8192.5, 9 * 12 * 32 * 128 * 992.03125
    assert per_token == pytest.approx(projections + experts + head + scores_global + scores_window)
    forward = per_token / 3  # ISSUE 38: 1.27 G a token forward, the two attentions' scores 43% of it (403 M and 146 M), projections 40%, experts 12%, head 4%
    assert forward == pytest.approx(1.27e9, rel=0.01)
    assert scores_global / 3 == pytest.approx(403e6, rel=0.01) and scores_window / 3 == pytest.approx(146e6, rel=0.01)
    assert (scores_global + scores_window) / per_token == pytest.approx(0.43, abs=0.01)
    assert projections / per_token == pytest.approx(0.40, abs=0.01) and experts / per_token == pytest.approx(0.12, abs=0.005)
    assert 16384 * per_token == pytest.approx(62.5e12, rel=0.01), "required operations a step (ISSUE 38's 82 T counts each block's forward twice: full remat)"
    fewer = count(SHAPE, {**RUN, "pairs_held_per_token": 0.5})["ops_per_token"]
    assert per_token - fewer == pytest.approx(6 * 12 * 0.5 * 6_193_152), "the routed work by the pairs held, as the counter read them"


def test_the_flash_kernels_are_counted_by_label_and_by_positions():
    count = load_module(REPO, "shapes", "flash_attention_window").count
    calls = count(SHAPE, RUN)
    u_window, u_global = 2.0 * 32 * 16_253_440 * 128, 2.0 * 32 * (16384 * 16385 // 2) * 128
    assert calls["flash_attention_window_fwd"]["ops"] == 2 * u_window and calls["flash_attention_window_bwd"]["ops"] == 4 * u_window
    assert calls["flash_attention_fwd"]["ops"] == 2 * u_global and calls["flash_attention_bwd"]["ops"] == 4 * u_global
    for prefix in ("flash_attention_", "flash_attention_window_"):  # the two kernels a long row falls back to share the fused backward's four products
        assert calls[prefix + "bwd_dq"]["ops"] + calls[prefix + "bwd_dkv"]["ops"] == calls[prefix + "bwd"]["ops"]
    # never by tiles: at 1024 x 1024 a windowed call walks 31 tiles' worth of 20 / 16 sub-squares... the required count is under what any tiling computes
    from modalities_tpu.ops.pallas.flash_attention import _rectangles, tile_plan

    plan = tile_plan(16384, 16384, 1024, 1024, True, 1024)
    walked = sum(sum(r * c for _, r, _, c, _ in _rectangles(int(flags) & 60, 1024, 1024)) for flags in plan.q_major[2])
    assert 16_253_440 < walked == (16 + 15) * 10 * 256 * 256 and 16_253_440 / walked == pytest.approx(0.80, abs=0.005)
    assert calls["flash_attention_window_fwd"]["bytes"] == calls["flash_attention_fwd"]["bytes"] == 2 * 2 * 16384 * 128 * (32 + 4)
