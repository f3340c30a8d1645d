"""`configs/config_qwen3_next_80b.yaml` (Qwen3-Next-80B-A3B-Instruct, uncut) executed end to end at a size this machine holds: a
twin that only REPLACES scalars and lists of the recipe (widths, depth and the layer types of the kept layers, mesh, run length; the
component graph is asserted unchanged) goes through the same components, train step and trainer as the dense recipe (`Main.run`,
as `python -m modalities_tpu run` calls it), on a dp_shard 2 mesh of CPU devices. The published intervals carry the expert
layers' counters, the balance term and the rule layers' mean decay and mean beta among them, beside the loss."""

import math

from tests.end2end_tests.test_acceptance_recipe_twins import CONFIGS, _derive_twin, _run, workdir  # noqa: F401

TOY = {
    "model_raw.config.n_layer": 2, "model_raw.config.n_embd": 128, "model_raw.config.n_head_q": 4, "model_raw.config.n_head_kv": 2,
    "model_raw.config.head_dim": 32, "model_raw.config.ffn_hidden": 384, "model_raw.config.vocab_size": 272,
    "model_raw.config.lm_head_chunk_size": 64, "model_raw.config.layer_types": ["linear_attention", "full_attention"],
    "model_raw.config.moe_config.n_routed_experts": 8, "model_raw.config.moe_config.num_experts_per_tok": 2,
    "model_raw.config.moe_config.moe_intermediate_size": 64, "model_raw.config.moe_config.shared_expert_intermediate_size": 64,
    "model_raw.config.gdn_config.linear_num_key_heads": 2, "model_raw.config.gdn_config.linear_num_value_heads": 4,
    "model_raw.config.gdn_config.linear_key_head_dim": 16, "model_raw.config.gdn_config.linear_value_head_dim": 16,
    "model_raw.config.attention_norm_config.config.ndim": 128, "model_raw.config.ffn_norm_config.config.ndim": 128,
    "model_raw.config.lm_head_norm_config.config.ndim": 128, "model_raw.config.attention_config.qk_norm_config.config.ndim": 32,
}


def test_qwen3_next_twin_trains_through_the_normal_path_and_publishes_its_counters(workdir):  # noqa: F811
    steps, seq, mbs, dp = 4, 64, 2, 2
    out = workdir / "twin_qwen3_next.yaml"
    twin = _derive_twin(CONFIGS / "config_qwen3_next_80b.yaml", {
        **TOY,
        "device_mesh.config.device_type": "cpu", "device_mesh.config.data_parallel_shard_degree": dp, "device_mesh.config.world_size": dp,
        "settings.step_profile.local_train_micro_batch_size": mbs, "settings.step_profile.sequence_length": seq,
        "settings.training_target.num_target_steps": steps, "settings.training_target.num_target_tokens": steps * mbs * seq * dp,
        "settings.intervals.training_log_interval_in_steps": 1, "settings.intervals.checkpointing_interval_in_steps": steps,
        "settings.intervals.evaluation_interval_in_steps": steps,
    }, out)
    model = twin["model_raw"]["config"]
    assert model["attn_output_gate"] is True and model["moe_config"]["shared_expert_gate"] is True and model["moe_config"]["scoring_func"] == "softmax"
    assert model["gdn_config"]["linear_conv_kernel_dim"] == 4 and model["rope_parameters"]["full_attention"]["partial_rotary_factor"] == 0.25
    assert all(model[name]["config"]["zero_centered"] for name in ("attention_norm_config", "ffn_norm_config", "lm_head_norm_config"))
    assert model["attention_config"]["qk_norm_config"]["config"]["zero_centered"] and model["use_weight_tying"] is False
    assert twin["optimizer"]["config"]["weight_decay_groups_excluded"] == ["embedding", "norm", "gdn_vectors", "shared_expert_gate"]
    rows = _run(out, "qwen3_next_twin", workdir)
    assert [r["num_train_steps_done"] for r in rows] == [1, 2, 3, 4]
    losses = [r["losses"]["train loss avg"] for r in rows]
    assert all(math.isfinite(v) for v in losses) and losses[-1] < losses[0] + 0.05
    tokens = mbs * seq * dp
    for r in rows:  # all 8 experts are held: every one of a token's 2 pairs lands on a held expert, in both layers
        assert abs(r["metrics"]["moe_pairs_held"] - 2 * tokens) < 0.01 * tokens and 0.9 < r["metrics"]["moe_aux_loss"] < 1.5
        assert 0.0 < r["metrics"]["gdn_decay_mean"] < 1.0 and 0.4 < r["metrics"]["gdn_beta_mean"] < 0.6
    assert "MFU" in rows[-1]["throughput_metrics"], "the calculator is built for this model (the rule's products, the gated attention's width)"
