"""`configs/config_ouro_2p6b.yaml` (Ouro-2.6B, uncut) executed end to end at a size this machine
holds: a twin that only REPLACES scalars of the recipe (widths, depth, mesh, run length; the
component graph is asserted unchanged) goes through the same components, train step and trainer
as the dense recipe (`Main.run`, as `python -m modalities_tpu run` calls it), on a dp_shard 2
mesh of CPU devices. The published intervals carry what the loss over the exits counts."""

import math

from tests.end2end_tests.test_acceptance_recipe_twins import CONFIGS, _derive_twin, _run, workdir  # noqa: F401

NORMS = ("attention_norm_config", "post_attention_norm_config", "ffn_norm_config", "post_ffn_norm_config", "lm_head_norm_config")
TOY = {
    "model_raw.config.n_layer": 3, "model_raw.config.n_embd": 128, "model_raw.config.n_head_q": 4, "model_raw.config.n_head_kv": 4,
    "model_raw.config.ffn_hidden": 384, "model_raw.config.vocab_size": 256, "model_raw.config.lm_head_chunk_size": 64,
}


def test_ouro_twin_trains_through_the_normal_path_and_publishes_its_counters(workdir):  # noqa: F811
    steps, seq, mbs, dp = 4, 64, 2, 2
    out = workdir / "twin_ouro.yaml"
    twin = _derive_twin(CONFIGS / "config_ouro_2p6b.yaml", {
        **TOY,
        "device_mesh.config.device_type": "cpu", "device_mesh.config.data_parallel_shard_degree": dp, "device_mesh.config.world_size": dp,
        "settings.step_profile.local_train_micro_batch_size": mbs, "settings.step_profile.sequence_length": seq,
        "settings.training_target.num_target_steps": steps, "settings.training_target.num_target_tokens": steps * mbs * seq * dp,
        "settings.intervals.training_log_interval_in_steps": 1, "settings.intervals.checkpointing_interval_in_steps": steps,
        "settings.intervals.evaluation_interval_in_steps": steps,
    }, out)
    model = twin["model_raw"]["config"]
    assert model["loop_config"] == {"total_ut_steps": 4, "exit_gate": True, "beta": 0.1, "early_exit_threshold": 1}, "the walks are the recipe's own"
    assert all(model[norm]["norm_type"] == "rms_norm" for norm in NORMS) and twin["loss_fn"]["variant_key"] == "looped_exit_loss"
    assert twin["optimizer"]["config"]["weight_decay_groups_excluded"] == ["embedding", "norm", "exit_gate"]
    rows = _run(out, "ouro_twin", workdir)
    assert [r["num_train_steps_done"] for r in rows] == [1, 2, 3, 4]
    losses = [r["losses"]["train loss avg"] for r in rows]
    assert all(math.isfinite(v) for v in losses) and losses[-1] < losses[0] + 0.05
    for r in rows:  # a fresh gate sits near 1/2: the exit distribution near 1/2, 1/4, 1/8, 1/8, whose mean is 1.875 and entropy 1.21
        assert 1.5 < r["metrics"]["loop_expected_exit"] < 2.3 and 1.0 < r["metrics"]["loop_gate_entropy"] < math.log(4) + 1e-6
        assert all(4.0 < r["metrics"][f"loop_exit_ce_{t}"] < 7.0 for t in (1, 2, 3, 4)), "ln 256 = 5.5"
    assert "MFU" in rows[-1]["throughput_metrics"], "the calculator is built for this model (a parameter counted once an application)"
