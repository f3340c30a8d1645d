"""The four-chip configuration against the one-chip one it is cut from, the cell's place in the manifest, what one chip
holds of its step, and what a check of the whole benchmark costs with this cell at its real cost. The manifest is read for
THIS cell's own entries and for "the accepted cells come first, in their order": a later cell appended after this one
turns nothing here red."""

import json

import yaml

from benchmark.manifest import load_cell
from benchmark.weights import DecoderShape
from tests.benchmark.accepted import ACCEPTED_CELLS, ACCEPTED_CONFIGS, DRIVER_SECONDS, REAL_COST_S, check_seconds, holds_at_least
from tests.benchmark.toy import REPO

CELL, CONFIG = "train-2p7b-4k-x4", "modalities-2p7b-x4"
CONFIG_DIR = REPO / "benchmark" / "configs" / CONFIG
# seconds a run of this cell takes on the four chips, warm and where everything compiles (my chip runs, PR 50: PERF.md section 6)
COST_S = (163, 270)  # 158-163 s warm (set-up 53-56, window 40, reference 17-20), 270 cold (set-up 118, reference 61)
OWN = {"collective_exposed_pct", "collective_exposed_dp_shard_pct", "collective_exposed_tp_pct", "collective_in_flight_pct",
       "collective_gb_per_step", "device_idle_max_pct"}
JOINED = {"train_tokens_per_s", "train_step_ms", "train_host_stall_pct", "device_idle_pct.train", "train_mfu_pct", "train_mfu_ref_pct",
          "setup_outside_spans_s", "setup_build_components_s", "setup_init_s", "setup_preflight_s", "setup_first_step_s", "setup_warm_steps_s",
          "setup_compile_miss_s", "setup_compile_hit_s", "train_host_work_ms", "train_loop_unspanned_pct",
          # the dense cell's scope metrics: `train_dense.json` leaves 0.26% of the cell's busy time unattributed (under 2%)
          "train_fwd_ms", "train_bwd_ms", "train_optimizer_ms", "train_attn_ms", "train_mlp_ms", "train_head_loss_ms", "train_layer_carry_ms",
          "train_unattributed_pct"}


def _flat(node, prefix=""):
    if isinstance(node, dict):
        for key, value in node.items():
            yield from _flat(value, f"{prefix}{key}.")
    elif isinstance(node, list):
        for i, value in enumerate(node):
            yield from _flat(value, f"{prefix}{i}.")
    else:
        yield prefix[:-1], node


def test_the_yaml_is_the_one_chip_configurations_but_for_depth_batch_and_mesh():
    dense = dict(_flat(yaml.safe_load((REPO / "benchmark" / "configs" / "modalities-2p7b-d6" / "train.yaml").read_text())))
    mesh = dict(_flat(yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())))
    assert set(dense) == set(mesh)
    assert {key: (dense[key], mesh[key]) for key in dense if dense[key] != mesh[key]} == {
        "model_raw.config.n_layer": (6, 32),  # the recipe's own depth: not a cut
        "settings.step_profile.local_train_micro_batch_size": (2, 1),
        "device_mesh.config.data_parallel_shard_degree": (1, 2),
        "device_mesh.config.tensor_parallel_degree": (1, 2),
        "device_mesh.config.world_size": (1, 4),
    }
    assert "remat_model" not in yaml.safe_load((CONFIG_DIR / "train.yaml").read_text()), "no rematerialization: the dense rules' blocks/block/ pattern holds"
    shape = DecoderShape.from_model_config(yaml.safe_load((CONFIG_DIR / "train.yaml").read_text())["model_raw"]["config"])
    assert (shape.n_layer, shape.n_embd, shape.n_head_q, shape.n_head_kv, shape.ffn_hidden, shape.vocab_size) == (32, 2560, 32, 8, 7680, 50304)
    assert shape.all_params() == 2_669_447_680  # what the program's own log counts


def test_meta_says_what_differs_from_the_source_and_that_the_depth_is_no_cut():
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    dense = next(c for c in manifest["configs"] if c["name"] == "modalities-2p7b-d6")
    meta = json.loads((CONFIG_DIR / "meta.json").read_text())
    assert entry["source"] == dense["source"] == meta["source"], "the dense configuration's source, to the letter"
    assert set(entry["reduced"]) == set(meta["reduced"]) == (set(dense["reduced"]) - {"n_layer"}) | {"tensor_parallel_degree"}
    assert "n_layer" not in entry["reduced"] and "32" in meta["not_reduced"]
    assert "14.78 GiB" in meta["memory_analysis"] and "v5e:2x2" in meta["memory_analysis"]
    assert {"stands_for", "assumed", "departures_from_upstream", "source_in_repo"} <= set(meta)


def test_the_traffic_is_packed_4ks_corpus_letter_for_letter():
    traffic = REPO / "benchmark" / "traffic"
    dense, mesh = (json.loads((traffic / f"{name}.json").read_text()) for name in ("packed-4k", "packed-4k-x4"))
    same = lambda mix: {k: v for k, v in mix.items() if k not in ("mode", "why")}  # noqa: E731
    assert same(dense) == same(mesh) and mesh["mode"] == "train_mesh"


def test_the_cell_joins_the_accepted_lists_after_the_accepted_cells_and_brings_its_own_metrics():
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    assert holds_at_least([w["name"] for w in manifest["workloads"]], [*ACCEPTED_CELLS, CELL])
    assert holds_at_least([c["name"] for c in manifest["configs"]], [*ACCEPTED_CONFIGS, CONFIG])
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "packed-4k-x4", 4)
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(1, len(manifest["workloads"]) // 4)
    listed = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"] if CELL in m.get("workloads", ())}
    assert holds_at_least(listed, OWN | JOINED)
    assert not {"fused_ce_roofline", "flash_attention_roofline"} & listed, "their counts are stale: a benchmark issue mends them first"
    for name in JOINED:  # appended to a shared list: the cells it held before come first, in the order they had
        cells = next(m for m in manifest["end_to_end"] + manifest["per_layer"] if m["name"] == name)["workloads"]
        assert cells.index(CELL) == len([c for c in cells if c in ACCEPTED_CELLS]) and [c for c in cells if c in ACCEPTED_CELLS] == [c for c in ACCEPTED_CELLS if c in cells]
    for name in OWN:
        metric = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert (metric["layer"], metric["moves"], metric["workloads"][0]) == ("sharding", "train_tokens_per_s", CELL)
        assert "mfu" not in name and "roofline" not in name
        assert json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text())["reader"] == "collectives"
    cell = load_cell(CELL, REPO)
    assert cell.mode == "train_mesh" and cell.chips == 4 and set(cell.spec["limits"]) == {
        "loss_rel_gap", "grad_norm_rel_gap", "grad_rel_error", "param_change_rel_gap", "loss_rise_over_window"}
    assert (cell.spec["warm_steps"], cell.spec["trace_after_steps"], cell.spec["trace_steps"]) == (8, 2, 4)


def test_what_one_chip_holds_of_a_step():
    cell = load_cell(CELL, REPO)
    raw = yaml.safe_load(cell.yaml_path.read_text())
    shape = DecoderShape.from_model_config(raw["model_raw"]["config"])
    share = cell.module("modes", "train_mesh").chip_share(raw, shape, 4096)
    assert share == {"sequence_length": 4096, "rows_per_chip": 1, "q_heads_per_chip": 16, "kv_heads_per_chip": 4, "sequence_share_per_chip": 2048,
                     "ce_rows_per_chip": 2048, "vocab_per_chip": 50304, "chips": 4, "dp_shard": 2, "tp": 2}
    # the share of the whole step's peak is over the four chips: the required operations of all 8,192 tokens of a step
    ops = cell.module("shapes", "dense_decoder_required_ops").count(shape, share)["ops_per_token"]
    assert ops == 6 * shape.matmul_params() + 6 * 32 * 4096 * 2560


def test_a_check_of_the_whole_benchmark_with_this_cell_at_its_real_cost_fits_half_the_drivers_time():
    """A run of this cell is a float32 reference over four chips behind a window of 40 s: its real cost a run, warm
    and cold, stands in this file (`accepted.REAL_COST_S` is a file of the accepted benchmark, and holds the others')."""
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in manifest["workloads"]]
    long_costs = [REAL_COST_S[c] for c in cells if c in REAL_COST_S] + [COST_S]
    usual = len(cells) - len(long_costs)
    assert CELL not in REAL_COST_S and usual >= 0
    assert check_seconds(manifest["run_seconds"], usual, long_costs) <= DRIVER_SECONDS // 2
