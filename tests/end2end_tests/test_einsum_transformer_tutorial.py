"""The einsum_transformer tutorial flow: a CUSTOM MODEL registered via
Main.add_custom_component trains through the full config-driven app (the
library-extension contract, reference tutorials/einsum_transformer + library_usage)."""

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest
import yaml

from modalities_tpu.main import Main
from tests.end2end_tests.test_main_e2e import CONFIG, workdir  # noqa: F401 — fixture

TUTORIAL = Path(__file__).parent.parent.parent / "tutorials" / "einsum_transformer"


def _load_tutorial_module():
    spec = importlib.util.spec_from_file_location(
        "einsum_transformer", TUTORIAL / "einsum_transformer.py"
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["einsum_transformer"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_einsum_transformer_trains_via_custom_component(workdir):  # noqa: F811
    mod = _load_tutorial_module()

    cfg = yaml.safe_load(CONFIG.read_text())
    cfg["model_raw"] = {
        "component_key": "model",
        "variant_key": "einsum_transformer",
        "config": {
            "sample_key": "input_ids",
            "prediction_key": "logits",
            "vocab_size": 256,
            "sequence_length": 64,
            "n_layer": 2,
            "n_head": 4,
            "n_embd": 128,
            "ffn_hidden": 256,
        },
    }
    # the custom model skips the gpt2-specific init routine; keep fsdp2 wrap + raw chain
    cfg["model"] = {"instance_key": "sharded_model", "pass_type": "BY_REFERENCE"}
    del cfg["mfu_calculator"]
    config_path = workdir / "einsum_config.yaml"
    config_path.write_text(yaml.safe_dump(cfg, sort_keys=False))

    main = Main(config_path, experiments_root_path=workdir / "data" / "experiments",
                experiment_id="einsum")
    main.add_custom_component(
        "model", "einsum_transformer", mod.EinsumTransformer, mod.EinsumTransformerConfig
    )
    components = main.build_components()
    main.run(components)

    results = workdir / "data" / "experiments" / "einsum" / "evaluation_results.jsonl"
    train = [
        json.loads(line)
        for line in results.read_text().splitlines()
        if json.loads(line)["dataloader_tag"] == "train"
    ]
    losses = [r["losses"]["train loss avg"] for r in train]
    assert train[-1]["num_train_steps_done"] == 8
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], f"custom einsum model did not train: {losses}"
