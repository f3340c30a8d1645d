"""chip_smoke.py off the chip: its phases driven at toy size on the CPU (2 layers,
width 128), through the same `run` and `serve` entry points; the refusal without a
TPU; and that the two smoke configs are the 2.7B recipe at its published widths.
What the script is for — the program on a v5e — only the chip can show."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402

TOY_MODEL = {"vocab_size": 512, "n_layer": 2, "n_head_q": 4, "n_head_kv": 2, "n_embd": 128, "ffn_hidden": 384}
TOY_SEQ = 128


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    workdir = tmp_path_factory.mktemp("chip_smoke")
    serve_model = "serving_component.config.model.config"
    args = SimpleNamespace(
        seed=0, max_new_tokens=8, result_name=None,
        train_config=chip_smoke.derive_config(chip_smoke.TRAIN_CONFIG, workdir / "train.yaml", {
            **{f"model_raw.config.{key}": value for key, value in TOY_MODEL.items()},
            "model_raw.config.lm_head_chunk_size": 64,
            "settings.step_profile.sequence_length": TOY_SEQ,
            "settings.training_target.num_target_tokens": 8 * 2 * TOY_SEQ,
        }),
        serve_config=chip_smoke.derive_config(chip_smoke.SERVE_CONFIG, workdir / "serve.yaml", {
            **{f"{serve_model}.{key}": value for key, value in TOY_MODEL.items()},
            f"{serve_model}.sequence_length": TOY_SEQ,
            f"{serve_model}.attention_config.qkv_transforms.0.config.n_embd": TOY_MODEL["n_embd"],
            f"{serve_model}.attention_config.qkv_transforms.0.config.n_head": TOY_MODEL["n_head_q"],
            **{f"{serve_model}.{norm}.config.ndim": TOY_MODEL["n_embd"]
               for norm in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config")},
            "serving_component.config.paged_max_len": TOY_SEQ,
        }),
    )
    cwd = os.getcwd()
    os.chdir(workdir)  # the configs' paths are relative, as on the chip
    try:
        results = {}
        for phase in ("prep", "train", "step", "serve"):
            results[phase] = chip_smoke.PHASES[phase](workdir, args)
            (workdir / f"phase_{phase}.json").write_text(json.dumps(results[phase]))
    finally:
        os.chdir(cwd)
    return results


def test_train_phase_takes_its_steps_and_seals_a_checkpoint(toy):
    train = toy["train"]
    assert train["steps"] == 8 and len(train["losses"]) == 8
    assert abs(train["losses"][0] - train["ln_vocab"]) < chip_smoke.FIRST_LOSS_BAND
    assert Path(train["checkpoint"], "manifest.json").is_file()
    assert train["compiles"]["train_step"]["count"] >= 1


def test_step_phase_times_the_same_jitted_step_under_both_fences(toy):
    step = toy["step"]
    assert len(step["s_per_step_block_until_ready"]) == len(step["s_per_step_hard_sync"]) == chip_smoke.FENCE_STEPS
    assert step["kernels"] == {}  # a CPU program holds no tpu_custom_call
    assert step["cache_dir"].endswith(".jax_compilation_cache") or os.environ.get("JAX_COMPILATION_CACHE_DIR")


def test_serve_phase_answers_every_request_and_agrees_with_model_apply(toy):
    serve = toy["serve"]
    assert serve["requests"] == len(chip_smoke.PROMPT_TOKENS)
    assert serve["tokens_served"] == len(chip_smoke.PROMPT_TOKENS) * 8
    assert serve["engine_stats"]["decode_executables"] == 1
    assert serve["engine_stats"]["free_blocks"] == serve["engine_stats"]["num_blocks"]
    assert serve["reference"]["exact_argmax"] == serve["reference"]["tokens"] == 8


def test_without_a_tpu_the_script_fails_and_prints_no_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--workdir", str(tmp_path / "work")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU here" in proc.stderr and '"ok"' not in proc.stdout
    assert not (tmp_path / "work" / "data").exists(), "nothing may run on the CPU in the chip's place"


def test_smoke_configs_keep_the_recipes_widths_and_agree_with_each_other():
    recipe = chip_smoke.read_config(REPO / "configs" / "config_2p7b_dp.yaml")["model_raw"]["config"]
    train = chip_smoke.read_config(chip_smoke.TRAIN_CONFIG)
    served = chip_smoke.read_config(chip_smoke.SERVE_CONFIG)["serving_component"]["config"]["model"]["config"]
    trained = train["model_raw"]["config"]
    widths = ("vocab_size", "n_head_q", "n_head_kv", "n_embd", "ffn_hidden", "attention_implementation",
              "activation_type", "use_weight_tying", "poe_type", "bias")
    assert {k: trained[k] for k in widths} == {k: recipe[k] for k in widths}
    assert {k: served[k] for k in widths + ("n_layer", "lm_head_chunk_size")} == {
        k: trained[k] for k in widths + ("n_layer", "lm_head_chunk_size")}
    assert served["sequence_length"] == train["settings"]["step_profile"]["sequence_length"] == 4096
    assert train["performance"] == chip_smoke.read_config(REPO / "configs" / "config_2p7b_dp.yaml")["performance"]
