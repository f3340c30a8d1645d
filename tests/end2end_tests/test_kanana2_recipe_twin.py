"""`configs/config_kanana2_30b_a3b.yaml` (kanana-2-30b-a3b-instruct-2601, uncut) executed end to
end at a size this machine holds: a twin that only REPLACES scalars of the recipe (widths,
depth, mesh, run length; the component graph is asserted unchanged) goes through the same
components, train step and trainer as the dense recipe (`Main.run`, as `python -m
modalities_tpu run` calls it), on a dp_shard 2 mesh of CPU devices. The published
intervals carry the expert layers' counters beside the loss."""

import math

from tests.end2end_tests.test_acceptance_recipe_twins import CONFIGS, _derive_twin, _run, workdir  # noqa: F401

TOY = {
    "model_raw.config.n_layer": 3, "model_raw.config.n_embd": 128, "model_raw.config.n_head_q": 4, "model_raw.config.n_head_kv": 4,
    "model_raw.config.ffn_hidden": 384, "model_raw.config.vocab_size": 256, "model_raw.config.lm_head_chunk_size": 64,
    "model_raw.config.mla_config.kv_lora_rank": 64, "model_raw.config.mla_config.qk_nope_head_dim": 32,
    "model_raw.config.mla_config.qk_rope_head_dim": 16, "model_raw.config.mla_config.v_head_dim": 32,
    "model_raw.config.moe_config.n_routed_experts": 8, "model_raw.config.moe_config.num_experts_per_tok": 3,
    "model_raw.config.moe_config.moe_intermediate_size": 64, "model_raw.config.moe_config.n_shared_experts": 1,
}


def test_kanana2_twin_trains_through_the_normal_path_and_publishes_its_counters(workdir):  # noqa: F811
    steps, seq, mbs, dp = 4, 64, 2, 2
    out = workdir / "twin_kanana2.yaml"
    twin = _derive_twin(CONFIGS / "config_kanana2_30b_a3b.yaml", {
        **TOY,
        "device_mesh.config.device_type": "cpu", "device_mesh.config.data_parallel_shard_degree": dp, "device_mesh.config.world_size": dp,
        "settings.step_profile.local_train_micro_batch_size": mbs, "settings.step_profile.sequence_length": seq,
        "settings.training_target.num_target_steps": steps, "settings.training_target.num_target_tokens": steps * mbs * seq * dp,
        "settings.intervals.training_log_interval_in_steps": 1, "settings.intervals.checkpointing_interval_in_steps": steps,
        "settings.intervals.evaluation_interval_in_steps": steps,
    }, out)
    assert twin["model_raw"]["config"]["moe_config"]["first_k_dense_replace"] == 1, "the rule for which layers are dense is the recipe's own"
    assert twin["optimizer"]["config"]["weight_decay_groups_excluded"] == ["embedding", "norm", "router_bias"]
    rows = _run(out, "kanana2_twin", workdir)
    assert [r["num_train_steps_done"] for r in rows] == [1, 2, 3, 4]
    losses = [r["losses"]["train loss avg"] for r in rows]
    assert all(math.isfinite(v) for v in losses) and losses[-1] < losses[0] + 0.05
    tokens = mbs * seq * dp
    for r in rows:  # all 8 experts are held: every one of a token's 3 pairs lands on a held expert, in both expert layers
        assert r["metrics"]["moe_pairs_held"] == 3 * tokens and r["metrics"]["moe_load_mean"] == 3 * tokens / 8
        assert 3 * tokens / 8 <= r["metrics"]["moe_load_max"] <= tokens
    assert "MFU" in rows[-1]["throughput_metrics"], "the calculator is built for this model (active parameters, two head sizes)"
