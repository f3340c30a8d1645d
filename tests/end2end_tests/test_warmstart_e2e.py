"""Config-driven warmstart end to end, including the reference's strongest oracle
(test_fsdp2_warmstart_pp_tp.py:48-60): train under PP x TP with the scheduled 1F1B
executor, resume the checkpoint under pure DP — progress is parsed from the folder
name, the sampler fast-skips, and training continues to the extended target."""

import json
from pathlib import Path

import numpy as np
import pytest

from modalities_tpu.main import Main
from tests.end2end_tests.test_main_e2e import workdir  # noqa: F401 — fixture

PP_TP_CONFIG = Path(__file__).parent.parent.parent / "configs" / "config_lorem_ipsum_tpu_pp_tp.yaml"
WARMSTART_CONFIG = (
    Path(__file__).parent.parent.parent / "configs" / "config_lorem_ipsum_tpu_warmstart.yaml"
)


def _run(config_path, experiment_id, workdir, resolver=None):  # noqa: F811
    main = Main(
        config_path,
        experiments_root_path=workdir / "data" / "experiments",
        experiment_id=experiment_id,
        additional_resolver_funs=resolver,
    )
    components = main.build_components()
    main.run(components)
    results = workdir / "data" / "experiments" / experiment_id / "evaluation_results.jsonl"
    return [json.loads(line) for line in results.read_text().splitlines()]


def test_warmstart_pp_tp_to_dp_continues_training(workdir):  # noqa: F811
    # phase 1: 8 steps under pp2 x dp2 x tp2 with the scheduled 1F1B executor
    lines = _run(PP_TP_CONFIG, "phase1", workdir)
    train = [r for r in lines if r["dataloader_tag"] == "train"]
    assert train[-1]["num_train_steps_done"] == 8
    phase1_last_loss = train[-1]["losses"]["train loss last"]

    info_file = workdir / "data" / "checkpoints" / "last_checkpoint_info.json"
    info = json.loads(info_file.read_text())
    assert "seen_steps_8-" in info["checkpoint_folder_path"]

    # phase 2: resume that checkpoint on a PURE-DP mesh to the extended target
    def warmstart_env(key: str):
        return info["checkpoint_folder_path"]

    lines2 = _run(
        WARMSTART_CONFIG, "phase2", workdir, resolver={"warmstart_env": warmstart_env}
    )
    train2 = [r for r in lines2 if r["dataloader_tag"] == "train"]
    # picked up at step 8 and ran to the extended target (12); tokens kept counting
    assert train2[0]["num_train_steps_done"] > 8
    assert train2[-1]["num_train_steps_done"] == 12
    assert train2[-1]["metrics"]["consumed tokens"] == 8192 + 4 * 4096
    assert train2[-1]["losses"]["train loss avg"] < phase1_last_loss
    assert all(np.isfinite(r["losses"]["train loss avg"]) for r in train2)


@pytest.mark.slow  # ~38 s; CoCa training itself is pinned by tests/models/test_coca_vit.py
def test_coca_example_config_trains(workdir):  # noqa: F811
    """The CoCa multimodal example config (reference config_example_coca.yaml) runs
    through the full app: dummy image+text data, CoCa collator, ViT+decoders, real
    checkpointing — the multimodal counterpart of the GPT2 e2e run."""
    np.random.seed(0)  # DummyDataset draws from the global numpy RNG
    coca_config = Path(__file__).parent.parent.parent / "configs" / "config_example_coca_tpu.yaml"
    # widen the horizon to the dataset maximum (384 samples = 12 steps x 4 mbs x
    # 8 dp, exactly one epoch) so the loss trace has 6 logged intervals instead
    # of 4 — the 8-step original flaked on a single-sample endpoint compare
    widened = workdir / "config_coca_12_steps.yaml"
    widened.write_text(
        coca_config.read_text()
        .replace("num_target_tokens: 4096   # 8 steps x 4 mbs x 16 seq x dp8", "num_target_tokens: 6144")
        .replace("num_target_steps: 8", "num_target_steps: 12")
    )
    lines = _run(widened, "coca", workdir)
    train = [r for r in lines if r["dataloader_tag"] == "train"]
    assert train[-1]["num_train_steps_done"] == 12
    losses = [r["losses"]["train loss avg"] for r in train]
    assert all(np.isfinite(losses))
    # The dummy targets are i.i.d. uniform over the 512-token vocab, so the CE
    # optimum is ln(512) ~= 6.238 and the model sits there from step 1 — there
    # is no signal to descend on. The real regression oracle is that training
    # HOLDS the optimum (an optimizer/sharding bug blows this band); the
    # windowed-mean trend stays as a determinism canary on the fixed seed.
    assert all(abs(loss - np.log(512.0)) < 0.05 for loss in losses), losses
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses
    assert any("seen_steps_12-" in p.name for p in (workdir / "data" / "checkpoints").iterdir())
