"""`configs/config_zaya1_8b.yaml` (ZAYA1-8B, uncut) executed end to end at a size this machine holds: a twin that only
REPLACES scalars and lists of the recipe (widths, depth and the layer types of the kept layers, mesh, run length; the
component graph is asserted unchanged) goes through the same components, train step and trainer as the dense recipe
(`Main.run`, as `python -m modalities_tpu run` calls it), on a dp_shard 2 mesh of CPU devices. The published intervals carry
the expert layers' counters, the skip share and the key temperature among them, beside the loss."""

import math

from tests.end2end_tests.test_acceptance_recipe_twins import CONFIGS, _derive_twin, _run, workdir  # noqa: F401

TOY = {
    "model_raw.config.n_layer": 3, "model_raw.config.n_embd": 128, "model_raw.config.n_head_q": 4, "model_raw.config.n_head_kv": 2,
    "model_raw.config.head_dim": 32, "model_raw.config.ffn_hidden": 384, "model_raw.config.vocab_size": 272,
    "model_raw.config.lm_head_chunk_size": 64, "model_raw.config.layer_types": ["hybrid", "hybrid", "hybrid"],
    "model_raw.config.moe_config.n_routed_experts": 4, "model_raw.config.moe_config.moe_intermediate_size": 64,
    "model_raw.config.moe_config.router_hidden_size": 32,
}


def test_zaya1_twin_trains_through_the_normal_path_and_publishes_its_counters(workdir):  # noqa: F811
    steps, seq, mbs, dp = 4, 64, 2, 2
    out = workdir / "twin_zaya1.yaml"
    twin = _derive_twin(CONFIGS / "config_zaya1_8b.yaml", {
        **TOY,
        "device_mesh.config.device_type": "cpu", "device_mesh.config.data_parallel_shard_degree": dp, "device_mesh.config.world_size": dp,
        "settings.step_profile.local_train_micro_batch_size": mbs, "settings.step_profile.sequence_length": seq,
        "settings.training_target.num_target_steps": steps, "settings.training_target.num_target_tokens": steps * mbs * seq * dp,
        "settings.intervals.training_log_interval_in_steps": 1, "settings.intervals.checkpointing_interval_in_steps": steps,
        "settings.intervals.evaluation_interval_in_steps": steps,
    }, out)
    model = twin["model_raw"]["config"]
    assert model["moe_config"]["router"] == "mlp" and model["moe_config"]["use_eda"] and model["moe_config"]["use_mod"], "the router is the recipe's own"
    assert model["cca_config"] == {"cca_time0": 2, "cca_time1": 2} and model["scale_residual_merge"] is True
    assert model["rope_parameters"]["hybrid"]["partial_rotary_factor"] == 0.5 and model["use_weight_tying"] is True
    assert twin["optimizer"]["config"]["weight_decay_groups_excluded"] == ["embedding", "norm", "router_bias", "cca_vectors", "residual_merge", "router_vectors"]
    rows = _run(out, "zaya1_twin", workdir)
    assert [r["num_train_steps_done"] for r in rows] == [1, 2, 3, 4]
    losses = [r["losses"]["train loss avg"] for r in rows]
    assert all(math.isfinite(v) for v in losses) and losses[-1] < losses[0] + 0.05
    tokens = mbs * seq * dp
    for r in rows:  # all 4 experts are held: a token's one pair lands on a held expert unless it chose the skip column, in all three layers
        assert abs(r["metrics"]["moe_pairs_held"] - (1 - r["metrics"]["moe_skip_share"]) * tokens) < 0.01 * tokens  # both published rounded
        assert 0.0 <= r["metrics"]["moe_skip_share"] <= 1.0 and 0.9 < r["metrics"]["cca_key_temperature"] < 1.1
    assert "MFU" in rows[-1]["throughput_metrics"], "the calculator is built for this model (the latent's width, the router's columns)"
