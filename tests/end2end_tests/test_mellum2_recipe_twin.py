"""`configs/config_mellum2_12b_a2p5b.yaml` (Mellum2-12B-A2.5B-Instruct, uncut) executed end to end at a size this
machine holds: a twin that only REPLACES scalars and lists of the recipe (widths, depth and the layer types of the kept
layers, the window, mesh, run length; the component graph is asserted unchanged) goes through the same components, train
step and trainer as the dense recipe (`Main.run`, as `python -m modalities_tpu run` calls it), on a dp_shard 2 mesh of CPU
devices. The published intervals carry the expert layers' counters, the balance term among them, beside the loss."""

import math

from tests.end2end_tests.test_acceptance_recipe_twins import CONFIGS, _derive_twin, _run, workdir  # noqa: F401

TOY = {
    "model_raw.config.n_layer": 4, "model_raw.config.n_embd": 128, "model_raw.config.n_head_q": 4, "model_raw.config.n_head_kv": 2,
    "model_raw.config.head_dim": 48, "model_raw.config.ffn_hidden": 384, "model_raw.config.vocab_size": 256,
    "model_raw.config.lm_head_chunk_size": 64, "model_raw.config.sliding_window": 16,
    "model_raw.config.layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
    "model_raw.config.rope_parameters.full_attention.original_max_position_embeddings": 32,
    "model_raw.config.moe_config.n_routed_experts": 8, "model_raw.config.moe_config.num_experts_per_tok": 3,
    "model_raw.config.moe_config.moe_intermediate_size": 64, "model_raw.config.moe_config.router_aux_loss_coef": 0.01,
}


def test_mellum2_twin_trains_through_the_normal_path_and_publishes_its_counters(workdir):  # noqa: F811
    steps, seq, mbs, dp = 4, 64, 2, 2
    out = workdir / "twin_mellum2.yaml"
    twin = _derive_twin(CONFIGS / "config_mellum2_12b_a2p5b.yaml", {
        **TOY,
        "device_mesh.config.device_type": "cpu", "device_mesh.config.data_parallel_shard_degree": dp, "device_mesh.config.world_size": dp,
        "settings.step_profile.local_train_micro_batch_size": mbs, "settings.step_profile.sequence_length": seq,
        "settings.training_target.num_target_steps": steps, "settings.training_target.num_target_tokens": steps * mbs * seq * dp,
        "settings.intervals.training_log_interval_in_steps": 1, "settings.intervals.checkpointing_interval_in_steps": steps,
        "settings.intervals.evaluation_interval_in_steps": steps,
    }, out)
    model = twin["model_raw"]["config"]
    assert model["moe_config"]["scoring_func"] == "softmax" and model["moe_config"]["topk_method"] == "greedy", "the router is the recipe's own"
    assert model["rope_parameters"]["full_attention"]["rope_type"] == "yarn" and model["rope_parameters"]["full_attention"]["factor"] == 16
    assert twin["optimizer"]["config"]["weight_decay_groups_excluded"] == ["embedding", "norm"]
    rows = _run(out, "mellum2_twin", workdir)
    assert [r["num_train_steps_done"] for r in rows] == [1, 2, 3, 4]
    losses = [r["losses"]["train loss avg"] for r in rows]
    assert all(math.isfinite(v) for v in losses) and losses[-1] < losses[0] + 0.05
    tokens = mbs * seq * dp
    for r in rows:  # all 8 experts are held: every one of a token's 3 pairs lands on a held expert, in all four layers
        assert r["metrics"]["moe_pairs_held"] == 3 * tokens and r["metrics"]["moe_load_mean"] == 3 * tokens / 8
        assert 1.0 <= r["metrics"]["moe_aux_loss"] < 8 / 3, "1 at balance, E / k where k experts take everything"
    assert "MFU" in rows[-1]["throughput_metrics"], "the calculator is built for this model (a window's positions, the head's own width)"
