"""A copy of the benchmark at toy size, for rehearsals on the CPU: the harness's own
files under a temporary root, its YAMLs cut to 2 layers of width 128 and its corpus to
short documents. Nothing here is measured; the chip measures the real cells."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import yaml

REPO = Path(__file__).resolve().parents[2]
TOY_MODEL = {"vocab_size": 512, "n_layer": 2, "n_head_q": 4, "n_head_kv": 2, "n_embd": 128, "ffn_hidden": 384}
TOY_SEQ = 128


def _shrink_model(model: dict) -> None:
    model.update(TOY_MODEL)
    model["sequence_length"] = TOY_SEQ
    model["lm_head_chunk_size"] = 64
    rotary = model["attention_config"]["qkv_transforms"][0]["config"]
    rotary["n_embd"], rotary["n_head"] = TOY_MODEL["n_embd"], TOY_MODEL["n_head_q"]
    for norm in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"):
        model[norm]["config"]["ndim"] = TOY_MODEL["n_embd"]


def make_toy_root(dst: Path) -> Path:
    """`dst` becomes a root the harness can run from: BENCHMARK.json + benchmark/."""
    dst = Path(dst)
    shutil.copytree(REPO / "benchmark", dst / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for path in (dst / "benchmark" / "configs").glob("*/train.yaml"):
        raw = yaml.safe_load(path.read_text())
        _shrink_model(raw["model_raw"]["config"])
        raw["model_raw"]["config"]["sequence_length"] = "${settings.step_profile.sequence_length}"
        raw["settings"]["step_profile"]["sequence_length"] = TOY_SEQ
        profile, mesh = raw["settings"]["step_profile"], raw["device_mesh"]["config"]
        raw["settings"]["training_target"] = {
            "num_target_steps": 64,
            "num_target_tokens": 64 * profile["local_train_micro_batch_size"] * mesh["data_parallel_shard_degree"] * TOY_SEQ,
        }
        raw["device_mesh"]["config"]["device_type"] = "cpu"
        path.write_text(yaml.safe_dump(raw, sort_keys=False))
    for path in (dst / "benchmark" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(sequences=2 * 64 * 2, doc_len_median=40, doc_len_max=300, doc_len_min=4)
        path.write_text(json.dumps(mix))
    for path in (dst / "benchmark" / "workloads").glob("*.json"):
        spec = json.loads(path.read_text())
        spec.update(warm_steps=6)
        path.write_text(json.dumps(spec))
    return dst
