"""A decoder of `hybrid` layers (PR 40, `model_type: zaya`): attention in a compressed latent with two convolutions over the
sequence, a mean shared by q and k, an L2 norm with a key temperature, the rotary on half a head and half the value heads read
off the previous position (`models/gpt2/cca.py`); scaled residual merges; an expert layer whose router is an MLP over a state
handed from layer to layer, with one choice a token and a column that skips (`models/gpt2/moe.py`). Held to the plain reference
(benchmark/reference/cca_moe_decoder_f32.py) on the benchmark's seeded weights at toy widths: d 128, 4 query heads on 2
key/value heads of 32 (a latent of 192), taps 2 and 2, 16 of a head's 32 channels turned; a router state of 32; 8 experts of 128
and the skip column, one choice a token, 4 experts held from the third; three layers; a vocabulary of 528 rows (16 x 33: no
multiple of 128, as a chip's eighth of the source's table is none)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.core import meta

from benchmark.reference import cca_moe_decoder_f32 as reference
from benchmark.weights_cca_moe import CcaMoEShape, layer_weights, make_program_tree, reference_layout, seed_key
from modalities_tpu.models.gpt2 import cca, gpt2_model
from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM, GPT2LLMConfig, RopeSpec
from modalities_tpu.models.gpt2.moe import MoE, MoESpec, _MLPRouter
from modalities_tpu.ops.selective_scan import causal_depthwise_conv

SEED = 2**31 + 11
NORM = {"norm_type": "rms_norm", "config": {"ndim": 128, "bias": False, "epsilon": 1e-5}}
MOE = {"n_routed_experts": 8, "num_experts_per_tok": 1, "moe_intermediate_size": 128, "scoring_func": "softmax", "topk_method": "noaux_tc",
       "norm_topk_prob": False, "experts_held": 4, "expert_offset": 2, "router": "mlp", "router_hidden_size": 32, "use_eda": True,
       "use_mod": True, "bias_update_speed": 0.001}
ROPE = {"hybrid": {"rope_type": "default", "rope_theta": 5000000, "partial_rotary_factor": 0.5}}
LAYERS = 3
TOY = dict(
    sample_key="input_ids", prediction_key="logits", poe_type="NOPE", sequence_length=64, vocab_size=528, n_layer=LAYERS,
    n_head_q=4, n_head_kv=2, n_embd=128, head_dim=32, ffn_hidden=384, dropout=0.0, bias=False,
    attention_config={"qkv_transforms": [{"type_hint": "RotaryTransform", "config": {"n_embd": 128, "n_head": 4, "base_freq": 5000000}}]},
    attention_implementation="manual", activation_type="swiglu", attention_norm_config=NORM, ffn_norm_config=NORM,
    lm_head_norm_config=NORM, use_weight_tying=True, moe_config=MOE, layer_types=["hybrid"] * LAYERS, rope_parameters=ROPE,
    cca_config={"cca_time0": 2, "cca_time1": 2}, scale_residual_merge=True,
)
HIGHEST = jax.default_matmul_precision("highest")


def build(**changes) -> GPT2LLM:
    return GPT2LLM(**GPT2LLMConfig(**{**TOY, **changes}).model_dump())


def unboxed_shapes(model):
    return jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))


def shape_of(**changes) -> CcaMoEShape:
    return CcaMoEShape.from_yaml({"model_raw": {"config": {**TOY, **changes}}})


def stirred(params, scale=0.05):
    """Every small leaf (scales, shifts, biases, gates: ones and zeros as seeded) moved off its constant, so that each matters
    to the result; the selection bias by a tenth of that (it only orders)."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    out = []
    for (path, leaf), key in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        small = leaf.size <= LAYERS * 192 and "conv0_kernel" not in name
        by = scale * (0.01 if "e_score_correction_bias" in name else 1.0)
        out.append(leaf + by * jax.random.normal(key, leaf.shape) if small else leaf)
    return jax.tree.unflatten(tree, out)


@pytest.fixture(scope="module")
def toy():
    """The model computing in float32, its seeded weights (bfloat16 values, held in float32) with the constants stirred, and their shape."""
    model = build().with_spec_updates(compute_dtype="float32")
    shape = shape_of()
    params = make_program_tree(shape, SEED, unboxed_shapes(model), match_dtypes=False)
    return model, shape, stirred(jax.tree.map(lambda x: x.astype(jnp.float32), params))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 527, size=(2, 65)).astype(np.int32)


def layer_of(params, layer: int) -> dict:
    return {name: value[layer] for name, value in reference_layout(params)["runs"][0].items()}


# ------------------------------------------------------------------ config


def test_the_stack_is_one_run_that_carries_the_router_state(toy):
    model, shape, params = toy
    spec = model.config_spec
    assert spec.kinds == ("cca",) * LAYERS and spec.has_moe and not spec.has_window and not spec.has_ssm
    assert spec.stack_runs == (("cca", "moe", LAYERS),) and spec.router_state_width == 32 and spec.scale_residual_merge
    assert spec.head_dim == 32 and spec.rope_of("cca") == RopeSpec("default", 5000000.0, partial_rotary=0.5)
    assert gpt2_model.rotary_dim(32, spec.rope_of("cca")) == 16 and spec.moe.router_width == 9 and spec.counter_row_width == 3 + 9 + 1
    assert hash(spec) == hash(build().with_spec_updates(compute_dtype="float32").config_spec)
    assert sorted(params["params"]) == ["lm_head_norm", "run_0", "wte"], "the head is the table's"
    block = params["params"]["run_0"]["blocks"]["block"]
    assert sorted(block) == ["attention_norm", "attn_merge", "cca", "ffn_merge", "ffn_norm", "moe"]
    assert sorted(block["cca"]) == ["c_proj", "conv0_bias", "conv0_kernel", "conv1_bias", "conv1_kernel", "k_attn", "key_temperature", "q_attn", "v_attn", "v_attn_prev"]
    assert block["cca"]["conv1_kernel"].shape == (LAYERS, 2, 6, 32, 32) and block["cca"]["v_attn_prev"]["kernel"].shape == (LAYERS, 128, 1, 32)
    assert sorted(block["moe"]["router"]) == ["down", "e_score_correction_bias", "eda_gate", "fc1", "fc2", "norm_scale", "out"]
    assert block["moe"]["router"]["out"]["kernel"].shape == (LAYERS, 32, 9) and block["moe"]["router"]["e_score_correction_bias"].shape == (LAYERS, 9)
    assert model.counted == {"moe_pairs_held": (), "moe_load_max": (), "moe_load_mean": (), "moe_expert_load": (LAYERS, 9),
                             "moe_skip_share": (), "cca_key_temperature": ()}
    assert shape.all_params() == sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params))


def test_everything_unset_is_the_model_of_before():
    plain = {k: v for k, v in TOY.items() if k not in ("head_dim", "layer_types", "rope_parameters", "moe_config", "cca_config", "scale_residual_merge")}
    spec = GPT2LLM(**GPT2LLMConfig(**{**plain, "vocab_size": 512}).model_dump()).config_spec
    assert spec.cca is None and not spec.scale_residual_merge and spec.router_state_width == 0 and spec.layer_kinds == ()
    matrix = MoESpec.from_config({"n_routed_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 64})
    assert matrix.router == "matrix" and matrix.router_width == 8 and matrix.state_width == 0 and not matrix.skip_column and not matrix.counts_aux_loss
    assert MoESpec.from_config({"n_routed_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 64, "scoring_func": "softmax", "topk_method": "greedy"}).counts_aux_loss


@pytest.mark.parametrize("changes, match", [
    ({"cca_config": None}, "layer_types of hybrid and cca_config go together"),
    ({"layer_types": None, "rope_parameters": None}, "layer_types of hybrid and cca_config go together"),
    ({"layer_types": ["hybrid", "hybrid", "full_attention"]}, "every layer is hybrid or none"),
    ({"mla_config": {"kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32}}, "leave rope_parameters, layer_types and head_dim unset|cca_config beside mla_config"),
    ({"loop_config": {"total_ut_steps": 2}}, "loop_config walks ONE run|cca_config beside"),
    ({"sliding_window": 16}, "cca_config beside mla_config, loop_config, sliding_window"),
    ({"n_head_kv": 1}, "n_head_kv must be even"),
    ({"attention_config": {**TOY["attention_config"], "qk_norm_config": NORM}}, "leave qk_norm_config unset"),
    ({"rope_parameters": {"hybrid": {**ROPE["hybrid"], "partial_rotary_factor": 0.6}}}, "rotated part of a head must be even"),
    ({"rope_parameters": {"hybrid": {"rope_type": "yarn", "factor": 4, "original_max_position_embeddings": 32, "partial_rotary_factor": 0.5}}}, "yarn on part of a head is not written"),
    ({"rope_parameters": {"hybrid": {**ROPE["hybrid"], "truncate": False}}}, "truncate"),
    ({"cca_config": {"cca_time0": 2, "cca_time1": 2, "cca_time2": 2}}, "cca_time2"),
    ({"moe_config": {**MOE, "router_hidden_size": None}}, "needs router_hidden_size"),
    ({"moe_config": {**MOE, "router": "matrix"}}, "belong to the router of kind mlp"),
    ({"moe_config": {**MOE, "norm_topk_prob": True}}, "leaves the router\\s+no gradient"),
    ({"moe_config": {**MOE, "scoring_func": "sigmoid"}}, "router mlp scores by softmax"),
    ({"moe_config": {**MOE, "topk_method": "greedy", "bias_update_speed": 0.0}}, "router mlp scores by softmax"),
    ({"moe_config": {**MOE, "n_shared_experts": 1}}, "shared expert or leading dense layers"),
    ({"moe_config": {**MOE, "experts_held": 8, "expert_offset": 1}}, "exceeds n_routed_experts"),  # the skip column is nobody's to hold
    ({"vocab_size": 100}, "divisible by 128"),
    ({"vocab_size": 520}, "must be by 16"),
])
def test_what_is_not_written_is_refused_at_config_time(changes, match):
    with pytest.raises(ValueError, match=match):
        GPT2LLMConfig(**{**TOY, **changes})


def test_a_partial_rotary_is_the_hybrid_layers_alone():
    """Since PR 44 a `full_attention` layer's too (the quarter-head rotary of `qwen3_next`); a window layer's stays refused."""
    plain = {k: v for k, v in TOY.items() if k not in ("cca_config", "scale_residual_merge", "moe_config")}
    with pytest.raises(ValueError, match="written for hybrid layers"):
        GPT2LLMConfig(**{**plain, "layer_types": ["sliding_attention"] * LAYERS, "sliding_window": 16, "rope_parameters": {"sliding_attention": ROPE["hybrid"]}})
    GPT2LLMConfig(**{**plain, "layer_types": ["full_attention"] * LAYERS, "rope_parameters": {"full_attention": ROPE["hybrid"]}})


# ------------------------------------------------------------------ refused by name where it is not written


def test_the_mixer_is_refused_in_serving_by_what_serving_lacks(toy):
    model, _, params = toy
    for serve in (lambda: model.init_decode_cache(params, 1), lambda: model.init_slot_cache(params, 2, 32),
                  lambda: model.init_paged_cache(params, 8, 16)):
        with pytest.raises(NotImplementedError, match="cache of convolution and shift state"):
            serve()
    assert "serving/paged_cache.py" in gpt2_model._NO_CONV_AND_SHIFT_STATE_CACHE


@pytest.mark.parametrize("axis, match", [("context_parallel_axis", "halo exchange"), ("pipeline_axis", "second array across the stage boundary")])
def test_the_mixer_and_the_carried_state_are_refused_under_cp_and_pp(toy, tokens, axis, match):
    _, _, params = toy
    sharded = build().with_spec_updates(compute_dtype="float32", **{axis: "cp" if axis.startswith("context") else "pp"})
    with pytest.raises(NotImplementedError, match=match):
        jax.eval_shape(lambda p: sharded.apply(p, {"input_ids": jnp.asarray(tokens[:, :-1])}), params)


# ------------------------------------------------------------------ each step of the equations alone


@pytest.fixture(scope="module")
def latent():
    rng = np.random.default_rng(7)
    return jnp.asarray(rng.normal(size=(2, 24, 6, 32)), jnp.float32)  # [B, S, Hq + Hkv, d]


def test_the_shift_brings_in_zeros_and_the_previous_position(latent):
    moved = cca.shift_right(latent, 1)
    assert np.array_equal(np.asarray(moved[:, 0]), np.zeros_like(latent[:, 0])) and np.array_equal(np.asarray(moved[:, 1:]), np.asarray(latent[:, :-1]))
    assert np.array_equal(np.asarray(moved[0]), np.asarray(reference.earlier(latent[0], 1))) and cca.shift_right(latent, 0) is latent


@pytest.mark.parametrize("taps", [1, 2, 3])
def test_both_convolutions_are_the_references(latent, taps):
    rng = np.random.default_rng(taps)
    a, a_bias = jnp.asarray(rng.normal(size=(taps, 192)), jnp.float32), jnp.asarray(rng.normal(size=(192,)), jnp.float32)
    big, big_bias = jnp.asarray(rng.normal(size=(taps, 6, 32, 32)), jnp.float32), jnp.asarray(rng.normal(size=(6, 32)), jnp.float32)
    with HIGHEST:
        got0 = causal_depthwise_conv(latent.reshape(2, 24, 192), a, a_bias)
        got1 = cca.grouped_conv(latent, big, big_bias)
        for row in range(2):
            np.testing.assert_allclose(np.asarray(got0[row]), np.asarray(reference.depthwise_conv(latent[row].reshape(24, 192), a, a_bias)), atol=1e-5)
            np.testing.assert_allclose(np.asarray(got1[row]), np.asarray(reference.grouped_conv(latent[row], big, big_bias)), atol=2e-4)
    # written out for two taps: the first tap weighs the position before, the last the current one; channels mix inside a head only
    if taps == 2:
        t, g = 5, 3
        want = np.asarray(latent[0, t - 1, g]) @ np.asarray(big[0, g]) + np.asarray(latent[0, t, g]) @ np.asarray(big[1, g]) + np.asarray(big_bias[g])
        np.testing.assert_allclose(np.asarray(got1[0, t, g]), want, atol=2e-4)
        np.testing.assert_allclose(np.asarray(got0[0, 0]), np.asarray(a[1] * latent[0, 0].reshape(192) + a_bias), atol=1e-5)


def test_the_mean_shared_by_q_and_k(latent):
    q0, k0 = latent[:, :, :4], latent[:, :, 4:]
    mq, mk = cca.qk_mean(q0, k0)
    for i in range(4):
        np.testing.assert_allclose(np.asarray(mq[:, :, i]), np.asarray((q0[:, :, i] + k0[:, :, i // 2]) / 2), atol=1e-6)
    for j in range(2):
        np.testing.assert_allclose(np.asarray(mk[:, :, j]), np.asarray((mq[:, :, 2 * j] + mq[:, :, 2 * j + 1]) / 2), atol=1e-6)


def test_the_l2_norm_and_the_key_temperature(latent):
    tau = jnp.asarray([0.7, 1.3], jnp.float32)
    got = cca.l2_normalised(latent[:, :, 4:], tau[None, None, :, None] * np.sqrt(32))
    np.testing.assert_allclose(np.linalg.norm(np.asarray(got), axis=-1), np.broadcast_to(np.asarray(tau) * np.sqrt(32), (2, 24, 2)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(reference.l2_normalised(latent[0, :, 4:], tau[None, :, None] * np.sqrt(32))), atol=1e-5)
    assert not np.any(np.isnan(np.asarray(cca.l2_normalised(jnp.zeros((1, 2, 2, 32)), 1.0)))), "a zero vector stays zero"


def test_the_rotary_turns_the_first_half_of_a_head_and_passes_the_rest(latent):
    rope = RopeSpec("default", 5000000.0, partial_rotary=0.5)
    cos, sin = gpt2_model._rope_tables(gpt2_model.rotary_dim(32, rope), 24, 10000, rope=rope)
    assert cos.shape == (24, 16)
    got = gpt2_model.apply_rope(latent, cos, sin)
    assert np.array_equal(np.asarray(got[..., 16:]), np.asarray(latent[..., 16:])), "channels 16..31 pass"
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(reference.rotate_part(latent[0], 16, 5000000.0)), atol=1e-5)
    # written out: pair n of the 8 turns channels n and n + 8 by the angle p theta^(-2n/16)
    p, n = 7, 3
    angle = p * 5000000.0 ** (-2 * n / 16)
    x = np.asarray(latent[0, p, 0])
    np.testing.assert_allclose(float(got[0, p, 0, n]), x[n] * np.cos(angle) - x[n + 8] * np.sin(angle), atol=1e-5)
    np.testing.assert_allclose(float(got[0, p, 0, n + 8]), x[n + 8] * np.cos(angle) + x[n] * np.sin(angle), atol=1e-5)
    whole = gpt2_model._rope_tables(32, 24, 5000000)
    assert np.array_equal(np.asarray(gpt2_model.apply_rope(latent, *whole)), np.asarray(latent * whole[0][None, :, None, :] + gpt2_model._rotate_half(latent) * whole[1][None, :, None, :])), "tables as wide as a head: the rotary of before"


# ------------------------------------------------------------------ the sub-layers against the reference


def mixer_of(model, params, layer: int):
    leaves = jax.tree.map(lambda v: v[layer], params["params"]["run_0"]["blocks"]["block"]["cca"])
    return lambda leaves, h: cca.CompressedConvAttention(model.config_spec).apply({"params": leaves}, h)[0], leaves


def test_the_whole_mixer_and_its_gradients_are_the_references(toy):
    model, shape, params = toy
    h = jnp.asarray(np.random.default_rng(1).normal(size=(2, 64, 128)), jnp.float32)
    apply, leaves = mixer_of(model, params, 1)
    w = layer_of(params, 1)
    with HIGHEST:  # each side one jitted program (PR 44: op by op, these four calls took 66 s of the suite's clock)
        got = jax.jit(apply)(leaves, h)
        want = jax.jit(jax.vmap(lambda row, w: reference.attention(row, w, shape), in_axes=(0, None)))(h, w)
        assert float(jnp.abs(want).max()) > 0.05 and float(jnp.abs(got - want).max()) < 1e-5
        probe = jnp.asarray(np.random.default_rng(2).normal(size=got.shape), jnp.float32)
        got_dw, got_dh = jax.jit(jax.grad(lambda l, h: jnp.sum(apply(l, h) * probe), argnums=(0, 1)))(leaves, h)
        want_dw, want_dh = jax.jit(jax.grad(lambda w, h: jnp.sum(jax.vmap(lambda row: reference.attention(row, w, shape))(h) * probe), argnums=(0, 1)))(w, h)
    assert float(jnp.abs(got_dh - want_dh).max()) < 1e-4 * float(jnp.abs(want_dh).max())
    named = {**{name: got_dw[name]["kernel"] for name in ("q_attn", "k_attn", "v_attn", "v_attn_prev", "c_proj")},
             **{name: got_dw[name] for name in ("conv0_kernel", "conv0_bias", "conv1_kernel", "conv1_bias", "key_temperature")}}
    for name, got_leaf in named.items():
        scale = float(jnp.abs(want_dw[name]).max())
        assert scale > 0 and float(jnp.abs(got_leaf - want_dw[name]).max()) < 2e-4 * scale, name
    assert float(cca.CompressedConvAttention(model.config_spec).apply({"params": leaves}, h)[1]) == pytest.approx(float(jnp.mean(leaves["key_temperature"])))


@pytest.mark.parametrize("without", ["no_conv", "no_value_shift", "no_qk_mean"])
def test_a_mixer_with_a_step_left_out_is_another_mixer(toy, without):
    """What `benchmark/tools/control_cca_moe.py --variant` runs: the reference's arithmetic with one step left out."""
    model, shape, params = toy
    h = jnp.asarray(np.random.default_rng(1).normal(size=(64, 128)), jnp.float32)
    w = layer_of(params, 1)
    with HIGHEST:
        want = reference.attention(h, w, shape)
        other = reference.attention(h, w, dataclasses.replace(shape, without=(without,)))
    assert float(jnp.abs(other - want).max()) > 0.02 * float(jnp.abs(want).max())
    if without == "no_value_shift":  # the first position has no previous one: it sees the shifted heads' values as zeros
        assert float(jnp.abs(other[0] - want[0]).max()) > 0


@pytest.mark.parametrize("handed", [False, True])
def test_the_router_with_and_without_a_state_handed_to_it(toy, handed):
    model, shape, params = toy
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(96, 128)), jnp.float32)
    previous = jnp.asarray(rng.normal(size=(96, 32)), jnp.float32) if handed else None
    leaves = jax.tree.map(lambda v: v[2], params["params"]["run_0"]["blocks"]["block"]["moe"]["router"])
    w = layer_of(params, 2)
    with HIGHEST:
        choice, weights, load, aux, state = _MLPRouter(model.config_spec.moe, 1e-5).apply({"params": leaves}, h, previous)
        probs, want_choice, want_weights, want_state = reference.route(h, w, previous, shape)
    assert aux is None and choice.shape == (96, 1) and np.array_equal(np.asarray(choice), np.asarray(want_choice))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want_weights), atol=1e-6)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state), atol=1e-5)
    assert np.asarray(load).tolist() == np.bincount(np.asarray(want_choice)[:, 0], minlength=9).tolist() and float(load.sum()) == 96
    np.testing.assert_allclose(np.asarray(probs.sum(axis=-1)), 1.0, atol=1e-6)
    if handed:  # the state handed on is taken after the sum and before the norm: it holds the previous one under the gate
        alone = _MLPRouter(model.config_spec.moe, 1e-5).apply({"params": leaves}, h, None)[4]
        np.testing.assert_allclose(np.asarray(state - alone), np.asarray(leaves["eda_gate"] * previous), atol=1e-5)


def test_the_merge_scales_and_shifts_both_sides_and_layer_zero_passes_the_embedding(toy):
    _, _, params = toy
    rng = np.random.default_rng(4)
    x, a = (jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.float32) for _ in range(2))
    leaves = jax.tree.map(lambda v: v[1], params["params"]["run_0"]["blocks"]["block"]["attn_merge"])
    w = layer_of(params, 1)
    merge = gpt2_model._ResidualMerge()
    got = merge.apply({"params": leaves}, x, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(reference.merge(x, a, w, "attn_merge")), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray((x + leaves["residual_bias"]) * leaves["residual_scale"] + (a + leaves["out_bias"]) * leaves["out_scale"]), atol=1e-6)
    for passes in (True, jnp.asarray(True)):
        first = merge.apply({"params": leaves}, x, a, passes)
        np.testing.assert_allclose(np.asarray(first), np.asarray(x + (a + leaves["out_bias"]) * leaves["out_scale"]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(first), np.asarray(reference.merge(x, a, w, "attn_merge", passes=True)), atol=1e-6)
    grads = jax.grad(lambda l: jnp.sum(merge.apply({"params": l}, x, a, jnp.asarray(True))))(leaves)
    assert not np.any(np.asarray(grads["residual_scale"])) and not np.any(np.asarray(grads["residual_bias"])) and np.any(np.asarray(grads["out_scale"]))


# ------------------------------------------------------------------ the stack against the reference


def logits_of(model, params, tokens):
    with HIGHEST:
        return np.asarray(jax.jit(lambda p, t: model.apply(p, {"input_ids": t})["logits"])(params, jnp.asarray(tokens[:, :-1])), np.float32)


def test_float32_program_is_the_reference_forward_on_the_seeded_weights(tokens):
    model, shape = build().with_spec_updates(compute_dtype="float32"), shape_of()
    params = jax.tree.map(lambda x: x.astype(jnp.float32), make_program_tree(shape, SEED, unboxed_shapes(model), match_dtypes=False))
    want = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens[:, :-1]))
    assert want.std() > 0.1 and np.abs(logits_of(model, params, tokens) - want).max() < 1e-5


@pytest.mark.parametrize("remat, scan", [(None, True), ("full", True), ("full", False)])
def test_scanned_rematerialized_and_unrolled_the_stack_computes_the_same(toy, tokens, remat, scan):
    """The layer scan carries the router's state beside the activations and hands the block its index; under full remat the
    block is traced with both as arguments; unrolled (`h_<i>`), the loop hands them on."""
    model, shape, params = toy
    other = build().with_spec_updates(compute_dtype="float32", remat_variant=remat, scan_layers=scan)
    if scan:
        got_params = params
    else:
        block = params["params"]["run_0"]["blocks"]["block"]
        got_params = {"params": {**{k: v for k, v in params["params"].items() if k != "run_0"},
                                 **{f"h_{i}": jax.tree.map(lambda v, i=i: v[i], block) for i in range(LAYERS)}}}
    ref = reference_layout(params)
    with HIGHEST:  # each side one jitted program (PR 44: op by op, the three cases took 70 s of the suite's clock)
        want = jax.jit(lambda ref: reference.batch_loss(ref, jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:]), shape))(ref)
        got, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(other, p, tokens)))(got_params)
    assert abs(float(got) - float(want)) < 2e-5 * float(want)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in jax.tree.leaves(grads))


def program_loss(model, params, tokens, with_parts=False):
    """Cross entropy as `training/train_step.py` composes it (this model hands no term up from its layers)."""
    hidden, counted = model.apply_counted(params, {"input_ids": jnp.asarray(tokens[:, :-1])}, train=True, hidden=True)
    logits = model.head_logits(params, hidden)
    loss = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits), jnp.asarray(tokens[:, 1:])[..., None], axis=-1))
    assert model.loss_from_layers(counted) is None
    return (loss, counted) if with_parts else loss


def test_loss_counters_and_every_leafs_gradient_are_the_references(toy, tokens):
    model, shape, params = toy
    ref_params = reference_layout(params)
    with HIGHEST:
        (loss, counted), grads = jax.jit(jax.value_and_grad(lambda p: program_loss(model, p, tokens, True), has_aux=True))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.batch_loss(p, jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:]), shape)))(ref_params)
        layers = [layer_of(params, i) for i in range(LAYERS)]
        _, _, loads = reference.loss_and_gradients(shape, layers, {name: ref_params[name] for name in reference.OUTER}, tokens[:, :-1], tokens[:, 1:])
    assert abs(float(loss) - float(want)) < 2e-5 * abs(float(want))
    assert np.asarray(counted["moe_expert_load"]).tolist() == loads.tolist() and loads.sum(axis=1).tolist() == [128.0] * LAYERS
    assert float(counted["moe_pairs_held"]) == pytest.approx(reference.pairs_held(shape, loads)) and float(counted["moe_skip_share"]) == pytest.approx(reference.skip_share(shape, loads))
    assert float(counted["cca_key_temperature"]) == pytest.approx(float(jnp.mean(ref_params["runs"][0]["key_temperature"])))
    got_leaves = reference_layout(grads)
    for name, want_leaf in want_grads["runs"][0].items():
        scale, got_leaf = float(jnp.abs(want_leaf).max()), got_leaves["runs"][0][name]
        if name == reference.BIAS or name in ("attn_merge_residual_scale", "attn_merge_residual_bias", "eda_gate"):
            # the selection bias only orders; layer 0's first merge passes the residual and nothing is handed to layer 0's router
            rows = slice(None) if name == reference.BIAS else slice(0, 1)
            assert not np.any(np.asarray(got_leaf[rows])) and not np.any(np.asarray(want_leaf[rows])), name
            if name == reference.BIAS:
                continue
        assert scale > 0 and float(jnp.abs(got_leaf - want_leaf).max()) < 3e-4 * scale, name
    for name in reference.OUTER:
        assert float(jnp.abs(got_leaves[name] - want_grads[name]).max()) < 3e-4 * float(jnp.abs(want_grads[name]).max()), name


def test_the_layer_by_layer_gradient_is_the_whole_models(toy, tokens):
    """`gradient_stream` (what the benchmark follows the program with: a layer at a time, the state's cotangent handed back
    beside the activation's) computes what `jax.grad` of `batch_loss` computes."""
    _, shape, params = toy
    ref_params = reference_layout(params)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    layers = [layer_of(params, i) for i in range(LAYERS)]
    loss, (grads, outer_grads), loads = reference.loss_and_gradients(shape, layers, {name: ref_params[name] for name in reference.OUTER}, inputs, targets)
    # one jitted program (PR 44: op by op this call took most of the test's 27 s)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: reference.batch_loss(p, jnp.asarray(inputs), jnp.asarray(targets), shape)))(ref_params)
    assert abs(loss - float(want)) < 1e-5 and loads.shape == (LAYERS, 9)
    named = reference.by_run(shape, grads, outer_grads)
    for name, leaf in want_grads["runs"][0].items():
        assert float(jnp.abs(named[f"run0.{name}"] - leaf).max()) < 1e-3 * max(float(jnp.abs(leaf).max()), 1e-6), name  # float32 sums in another order
    for name in reference.OUTER:
        assert float(jnp.abs(named[name] - want_grads[name]).max()) < 1e-3 * float(jnp.abs(want_grads[name]).max()), name


def test_two_steps_of_adamw_and_the_bias_rule_follow_the_reference(tokens):
    """The program's loss, gradient, AdamW with the model's own weight-decay groups and `after_update` (the selection bias's
    rule) over two steps, float32, against `reference.train_steps` from the same seeded weights."""
    from modalities_tpu.optimizers.optimizer_factory import build_weight_decay_mask

    model, shape = build().with_spec_updates(compute_dtype="float32", remat_variant="full"), shape_of()
    seeded = jax.tree.map(lambda x: x.astype(jnp.float32), make_program_tree(shape, SEED, unboxed_shapes(model), match_dtypes=False))
    rng = np.random.default_rng(9)
    batches = [rng.integers(0, 527, size=(2, 65)).astype(np.int32) for _ in range(2)]
    hyper = {"lr": [1.6e-4, 1.6e-4], "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}
    mask = build_weight_decay_mask(seeded, model, ["embedding", "norm", "router_bias", "cca_vectors", "residual_merge", "router_vectors"])
    decayed = {name for name, on in reference_layout(mask)["runs"][0].items() if on}
    assert decayed == set(reference.DECAYED) and not reference_layout(mask)["wte"] and not reference_layout(mask)["final_norm"]
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1.6e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, mask=mask))
    params, opt_state, losses = seeded, tx.init(seeded), []

    @jax.jit  # one compiled step for both batches (PR 44: op by op, the two steps took 70 s of the suite's clock)
    def step(params, opt_state, batch):
        (loss, counted), grads = jax.value_and_grad(lambda p: program_loss(model, p, batch, True), has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return model.after_update(optax.apply_updates(params, updates), counted), opt_state, loss

    with HIGHEST:
        for batch in batches:
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
        want = reference.train_steps(shape, SEED, [(b[:, :-1], b[:, 1:]) for b in batches], hyper)
    assert losses == pytest.approx(want["losses"], rel=3e-5)
    moved = reference.leaf_norms(jax.tree.map(lambda a, b: a - b, reference_layout(params), reference_layout(seeded)))
    for name, want_norm in want["delta_norms"].items():
        np.testing.assert_allclose(np.asarray(moved[name]), want_norm, rtol=2e-3, atol=1e-7, err_msg=name)
    assert np.all(np.asarray(want["delta_norms"]["run0.router_bias"]) <= 2 * 0.001 * 3 + 1e-9), "two moves of nine columns at most"


def test_the_shares_parts_add_up_to_the_uncut_layer(toy):
    """The guide's share test: two layers that each hold half of the 8 experts (0-3, 4-7) give parts of the expert
    sub-layer that add up to what the uncut reference gives, the skip column counted once, as nobody's: both shares
    see its load, neither adds anything for it, and the tokens that chose it get zero from the layer."""
    model, shape, params = toy
    whole = dataclasses.replace(shape, experts_held=8, expert_offset=0)
    w = {**layer_of(params, 1), **{k: v.astype(jnp.float32) for k, v in layer_weights(whole, seed_key(SEED), 1).items() if k.startswith("experts_")}}
    rng = np.random.default_rng(2)
    x, previous = jnp.asarray(rng.normal(size=(2, 64, 128)), jnp.float32), jnp.asarray(rng.normal(size=(2, 64, 32)), jnp.float32)
    router = jax.tree.map(lambda v: v[1], params["params"]["run_0"]["blocks"]["block"]["moe"]["router"])
    with HIGHEST:  # the skip column's bias lifted by what a quarter of the tokens lack to choose it, so that the test sees some
        probs = jax.vmap(lambda row, state: reference.route(row, w, state, whole)[0])(x, previous) + w[reference.BIAS]
    lacking = jnp.max(probs[..., :8], axis=-1) - probs[..., 8]
    lifted = router["e_score_correction_bias"].at[8].add(float(jnp.quantile(lacking, 0.25)))
    router, w = {**router, "e_score_correction_bias": lifted}, {**w, reference.BIAS: lifted}
    with HIGHEST:
        want, load, want_state = jax.jit(jax.vmap(lambda row, state: reference.expert_layer(row, w, state, whole)))(x, previous)  # jitted, as each share's below (PR 44: the suite's clock)
        load = load.sum(axis=0)
        total, held = jnp.zeros_like(want), []
        for offset in (0, 4):
            part = build(moe_config={**MOE, "expert_offset": offset}).with_spec_updates(compute_dtype="float32")
            leaves = {"router": router, "experts": {n: w[f"experts_{n}"][offset: offset + 4] for n in ("W", "V", "W_2")}}
            out, counters, state = jax.jit(lambda leaves, x, previous, part=part: MoE(part.config_spec).apply({"params": leaves}, x, previous))(leaves, x, previous)
            total, held = total + out, held + [float(counters[0])]
            assert np.asarray(counters[3:12]).tolist() == np.asarray(load).tolist(), "every share counts all 9 columns' loads"
            np.testing.assert_allclose(np.asarray(state), np.asarray(want_state), atol=1e-5)
    assert float(jnp.abs(total - want).max()) < 1e-5 * float(jnp.abs(want).max())
    assert held == [float(load[o: o + 4].sum()) for o in (0, 4)] and sum(held) + float(load[8]) == 2 * 64 and float(load[8]) > 0
    chose_skip = np.asarray(jax.vmap(lambda row, state: reference.route(row, w, state, whole)[1])(x, previous))[..., 0] == 8
    assert chose_skip.sum() == float(load[8]) and not np.any(np.asarray(total)[chose_skip]), "a token that skips gets zero from the layer"


@pytest.mark.parametrize("without", ["no_conv", "no_value_shift", "no_qk_mean", "no_eda", "full_rotary"])
def test_every_variant_of_the_control_is_another_model(toy, without):
    """One layer of the reference with a step of the equations left out (what `control_cca_moe.py --variant` follows two steps
    of), on rows with a state handed to it: another output than the layer's."""
    _, shape, params = toy
    other = dataclasses.replace(shape, rotated=32) if without == "full_rotary" else dataclasses.replace(shape, without=(without,))
    rng = np.random.default_rng(6)
    x, previous = jnp.asarray(rng.normal(size=(1, 64, 128)), jnp.float32), jnp.asarray(rng.normal(size=(1, 64, 32)), jnp.float32)
    w = layer_of(params, 1)
    with HIGHEST:
        want, want_state, _ = reference.layer_forward(w, x, previous, False, shape)
        got, got_state, _ = reference.layer_forward(w, x, previous, False, other)
    moved = float(jnp.abs(got_state - want_state).max()) if without == "no_eda" else float(jnp.abs(got - want).max())
    assert moved > 1e-3 * float(jnp.abs(want).max()), without


def test_the_required_operations_count_what_a_token_passes():
    """`utils/mfu.py`: a `cca` layer is an attention layer at the latent's width, the MLP router's matrices and the grouped
    convolution are parameters a token multiplies, and of the held experts a token passes one in `columns / held`."""
    from modalities_tpu.utils.mfu import GPT2MFUCalculator

    model, shape = build(), shape_of()
    calc = GPT2MFUCalculator(n_layer=LAYERS, sequence_length=64, n_embd=128, world_size=1, wrapped_model=model)
    assert calc.num_parameters == shape.all_params() and calc.n_attention_layer == LAYERS and calc.attention_width == 2 * 4 * 32
    assert calc.active_parameters == pytest.approx(shape.all_params() - LAYERS * (4 - 4 / 9) * shape.expert_params())


# ------------------------------------------------------------------ names on the trace


def test_the_new_scopes_are_on_the_operations_paths(toy, tokens):
    from modalities_tpu.telemetry import scopes

    model, _, params = toy
    text = jax.jit(jax.grad(lambda p: program_loss(model.with_spec_updates(remat_variant="full"), p, tokens))).lower(params).as_text(debug_info=True)
    for name in scopes.CCA_SCOPES + (scopes.ROPE, scopes.ATTN_CORE):
        assert f"block/{scopes.CCA}/{name}" in text, name
    assert f"{scopes.CCA}/{scopes.CCA_LATENT}/v_attn_prev" in text and f"{scopes.CCA}/{scopes.CCA_OUT}/c_proj" in text
    for name in scopes.ROUTER_MLP_SCOPES:
        assert f"{scopes.MOE}/{scopes.MOE_ROUTER}/{scopes.MOE_ROUTER}/{name}" in text, name
    assert f"block/{scopes.RESIDUAL}/attn_merge" in text and f"block/{scopes.RESIDUAL}/ffn_merge" in text
    assert "transpose(jvp(GPT2Module))" in text and "run_0/" in text and "rematted_computation" in text
