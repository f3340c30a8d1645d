"""Latent attention's and the expert layer's leaves under a mesh: their logical axes (`embed`
as elsewhere, heads and an expert's hidden dim over tp, the experts' axis, the latent and the
router's outputs replicated) let the train step compile and run under dp_shard 2 and under tp
2 on CPU devices, agree with one device, and publish the same counters. The dispatch runs
without an exchange: every chip of a mesh holds the same experts (there is no `ep` axis yet).
No cell measures this yet."""

import jax
import numpy as np
import pytest

from modalities_tpu.running_env.device_mesh import get_device_mesh
from modalities_tpu.training.train_step import COUNTER_PREFIX
from tests.models.test_moe_mla import build
from tests.training.test_train_step import _batch, _builder


def test_step_compiles_and_agrees_with_one_device_under_dp_shard_2_and_tp_2():
    raw = _batch(np.random.default_rng(3), 1, 2, 16, vocab=512)
    layouts = {"one_device": (1, {"data_parallel_shard_degree": 1}), "dp_shard_2": (2, {"data_parallel_shard_degree": 2}),
               "tp_2": (2, {"data_parallel_shard_degree": 1, "tensor_parallel_degree": 2})}
    losses, counters, sharded_over = {}, {}, {}
    for name, (world, layout) in layouts.items():
        model = build(sequence_length=16, n_layer=2, lm_head_chunk_size=8)
        fns = _builder(model, get_device_mesh(device_type="cpu", world_size=world, **layout), clip=1.0).build(seed=0)
        state = fns.app_state_handle.state
        block = state.params["params"]["run_1"]["blocks"]["block"]
        sharded_over[name] = {"experts_W": block["moe"]["experts"]["W"].sharding.spec, "experts_W_2": block["moe"]["experts"]["W_2"].sharding.spec,
                              "q_proj": block["attn"]["q_proj"]["kernel"].sharding.spec, "kv_a_proj": block["attn"]["kv_a_proj"]["kernel"].sharding.spec,
                              "router": block["moe"]["router"]["kernel"].sharding.spec}
        _, metrics = fns.train_step(state, fns.put_batch(raw))
        losses[name] = float(metrics["loss"])
        counters[name] = {k[len(COUNTER_PREFIX):]: float(v) for k, v in metrics.items() if k.startswith(COUNTER_PREFIX)}
    # [layers, experts, embed, expert_mlp]: `embed` over dp_shard, an expert's hidden dim over tp, the experts' axis on no axis
    assert sharded_over["dp_shard_2"]["experts_W"][2] == "dp_shard" and sharded_over["tp_2"]["experts_W"][3] == "tp"
    assert sharded_over["tp_2"]["experts_W_2"][2] == "tp" and sharded_over["tp_2"]["experts_W"][1] is None
    assert sharded_over["tp_2"]["q_proj"][2] == "tp" and "tp" not in tuple(sharded_over["tp_2"]["kv_a_proj"]), "heads over tp; the latent replicated"
    assert "tp" not in tuple(sharded_over["tp_2"]["router"])
    assert set(counters["one_device"]) == {"moe_pairs_held", "moe_load_max", "moe_load_mean"} and counters["one_device"]["moe_pairs_held"] > 0
    for name in ("dp_shard_2", "tp_2"):
        assert losses[name] == pytest.approx(losses["one_device"], rel=5e-3) and np.isfinite(losses[name])
        # the same tokens routed by the same router in float32: the counters agree to the token whose scores tie in bfloat16
        assert counters[name]["moe_pairs_held"] == pytest.approx(counters["one_device"]["moe_pairs_held"], abs=1.0)
