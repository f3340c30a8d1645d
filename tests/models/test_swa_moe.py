"""A decoder of window and global attention layers (`layer_types`, `sliding_window`), a rotary by kind of layer (YaRN on the
global ones), a head width of its own (`head_dim`) and a softmax-routed expert layer with a balance term (PR 38), held to the
plain reference (benchmark/reference/swa_moe_decoder_f32.py) on the benchmark's seeded weights at toy widths: d 128, 4 query
heads on 2 key/value heads of 48 (4 x 48 = 192, not 128), window 16 at sequence 64, two periods of three window layers and one
global layer; 16 experts of 64, 4 a token, 4 held from the fifth."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.reference import swa_moe_decoder_f32 as reference
from benchmark.weights_swa_moe import SwaMoEShape, layer_weights, make_program_tree, reference_layout, seed_key
from modalities_tpu.models.gpt2 import gpt2_model
from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM, GPT2LLMConfig, RopeSpec, rope_inv_freq, yarn_bounds
from modalities_tpu.models.gpt2.moe import MoE

SEED = 2**31 + 11
NORM = {"norm_type": "rms_norm", "config": {"ndim": 128, "bias": False, "epsilon": 1e-6}}
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
MOE = {"n_routed_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 64, "scoring_func": "softmax", "topk_method": "greedy",
       "experts_held": 4, "expert_offset": 4, "router_aux_loss_coef": 0.01}
TYPES = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 2
TOY = dict(
    sample_key="input_ids", prediction_key="logits", poe_type="NOPE", sequence_length=64, vocab_size=512, n_layer=8,
    n_head_q=4, n_head_kv=2, n_embd=128, head_dim=48, ffn_hidden=384, dropout=0.0, bias=False,
    attention_config={"qkv_transforms": [{"type_hint": "RotaryTransform", "config": {"n_embd": 128, "n_head": 4, "base_freq": 500000}}]},
    attention_implementation="manual", activation_type="swiglu", attention_norm_config=NORM, ffn_norm_config=NORM,
    lm_head_norm_config=NORM, use_weight_tying=False, moe_config=MOE, layer_types=TYPES, sliding_window=16,
    rope_parameters={"full_attention": YARN, "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
)


def build(**changes) -> GPT2LLM:
    return GPT2LLM(**GPT2LLMConfig(**{**TOY, **changes}).model_dump())


def unboxed_shapes(model):
    return jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))


def shape_of(**changes) -> SwaMoEShape:
    return SwaMoEShape.from_yaml({"model_raw": {"config": {**TOY, **changes}}})


@pytest.fixture(scope="module")
def toy():
    """The model computing in float32, its seeded weights (bfloat16 values, held in float32), and their shape."""
    model = build().with_spec_updates(compute_dtype="float32")
    shape = shape_of()
    params = make_program_tree(shape, SEED, unboxed_shapes(model), match_dtypes=False)
    return model, shape, jax.tree.map(lambda x: x.astype(jnp.float32), params)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 511, size=(2, 65)).astype(np.int32)


# ------------------------------------------------------------------ config


def test_the_stack_has_a_run_for_every_stretch_of_one_kind(toy):
    model, shape, params = toy
    spec = model.config_spec
    assert spec.kinds == ("swa", "swa", "swa", "attn") * 2 and spec.has_window and spec.has_moe and not spec.has_ssm
    assert spec.stack_runs == (("swa", "moe", 3), ("attn", "moe", 1)) * 2
    assert spec.head_dim == 48 and spec.sliding_window == 16
    assert spec.rope_of("swa") == RopeSpec("default", 500000.0) and spec.rope_of("attn").rope_type == "yarn"
    assert hash(spec) == hash(build().with_spec_updates(compute_dtype="float32").config_spec)
    assert sorted(params["params"]) == ["lm_head", "lm_head_norm", "run_0", "run_1", "run_2", "run_3", "wte"]
    block = params["params"]["run_0"]["blocks"]["block"]
    assert block["attn"]["q_attn"]["kernel"].shape == (3, 128, 4, 48) and block["attn"]["c_proj"]["kernel"].shape == (3, 4, 48, 128)
    assert sorted(block["moe"]) == ["experts", "router"] and sorted(block["moe"]["router"]) == ["kernel"], "no selection bias leaf, no shared expert"
    assert model.counted == {"moe_pairs_held": (), "moe_load_max": (), "moe_load_mean": (), "moe_expert_load": (8, 16), "moe_aux_loss": ()}
    assert shape.all_params() == sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params))


def test_everything_unset_is_the_model_of_before():
    plain = {k: v for k, v in TOY.items() if k not in ("head_dim", "layer_types", "sliding_window", "rope_parameters", "moe_config")}
    spec = GPT2LLM(**GPT2LLMConfig(**plain).model_dump()).config_spec
    assert spec.head_dim == 32 and spec.layer_kinds == () and not spec.has_window and spec.rope_of("attn") is None and spec.sliding_window is None


@pytest.mark.parametrize("changes, match", [
    ({"layer_types": TYPES[:3]}, "names 3 layers"),
    ({"sliding_window": None}, "give sliding_window"),
    ({"layer_types": None}, "sliding_window needs layer_types"),
    ({"head_dim": 47}, "head_dim must be even"),
    ({"attention_config": {"qkv_transforms": [{"type_hint": "IdentityTransform", "config": {}}]}}, "put a RotaryTransform"),
    ({"rope_parameters": {"full_attention": {"rope_type": "yarn", "factor": 16}}}, "original_max_position_embeddings"),
    ({"rope_parameters": {"full_attention": {**YARN, "truncate": False}}}, "truncate"),  # bounds not rounded: not written
    ({"moe_config": {**MOE, "scoring_func": "tanh"}}, "scoring_func"),
    ({"moe_config": {**MOE, "topk_method": "group_limited_greedy"}}, "topk_method"),
    ({"moe_config": {**MOE, "bias_update_speed": 0.1}}, "greedy has none"),
    ({"moe_config": {**MOE, "scoring_func": "sigmoid"}}, "router_aux_loss_coef weighs the balance term of a softmax router"),
    ({"loop_config": {"total_ut_steps": 2}, "moe_config": None}, "loop_config walks ONE run"),
])
def test_what_is_not_written_is_refused_at_config_time(changes, match):
    with pytest.raises(ValueError, match=match):
        GPT2LLMConfig(**{**TOY, **changes})


def test_yarn_on_latent_attention_is_refused_by_name():
    mla = {"kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32}
    latent = {**TOY, "n_head_kv": 4, "moe_config": None, "layer_types": None, "sliding_window": None, "head_dim": None,
              "attention_config": {"qkv_transforms": [{"type_hint": "IdentityTransform", "config": {}}]}, "mla_config": mla}
    with pytest.raises(ValueError, match="no YaRN"):
        GPT2LLMConfig(**{**latent, "rope_parameters": {"full_attention": YARN}})
    with pytest.raises(ValueError, match="yarn"):
        GPT2LLMConfig(**{**latent, "rope_parameters": None, "mla_config": {**mla, "rope_scaling": {"rope_type": "yarn", "factor": 16}}})


# ------------------------------------------------------------------ refused by name where it is not written


def test_window_layers_are_refused_in_serving_by_what_serving_lacks(toy):
    model, _, params = toy
    for serve in (lambda: model.init_decode_cache(params, 1), lambda: model.init_slot_cache(params, 2, 32),
                  lambda: model.init_paged_cache(params, 8, 16)):
        with pytest.raises(NotImplementedError, match="cache allocator by\\s+layer kind"):
            serve()
    assert "window in the decode and prefill masks" in gpt2_model._NO_CACHE_BY_LAYER_KIND


@pytest.mark.parametrize("axis, match", [("context_parallel_axis", "carries no window"), ("pipeline_axis", "stage plan that knows a layer's kind")])
def test_window_layers_are_refused_under_cp_and_pp(toy, tokens, axis, match):
    model, _, params = toy
    sharded = build().with_spec_updates(compute_dtype="float32", **{axis: "cp" if axis.startswith("context") else "pp"})
    with pytest.raises(NotImplementedError, match=match):
        jax.eval_shape(lambda p: sharded.apply(p, {"input_ids": jnp.asarray(tokens[:, :-1])}), params)


# ------------------------------------------------------------------ the rotary by kind


def test_yarn_tables_are_the_formula_written_in_numpy():
    """The source's numbers: theta 500000, factor 16 from 8192, beta 32 and 1, head 128: the ramp runs from pair 18 to pair 35."""
    rope = RopeSpec.from_config({**YARN, "original_max_position_embeddings": 8192})
    assert yarn_bounds(128, rope) == (18, 35) and rope.attention_factor == pytest.approx(0.1 * np.log(16) + 1, abs=1e-12)
    n = np.arange(64)
    turned = lambda b: 128 * np.log(8192 / (2 * np.pi * b)) / (2 * np.log(500000.0))  # noqa: E731
    assert (np.floor(turned(32)), np.ceil(turned(1))) == (18, 35)
    ramp = np.clip((n - 18) / (35 - 18), 0, 1)
    want = 500000.0 ** (-2 * n / 128) * ((1 - ramp) + ramp / 16)
    got = rope_inv_freq(128, rope)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert np.array_equal(got[:19], (500000.0 ** (-2 * n[:19] / 128)).astype(np.float32)), "the fast pairs are left as they are"
    np.testing.assert_allclose(got[35:], want[35:], rtol=1e-6)
    np.testing.assert_allclose(got[35:] * 16, 500000.0 ** (-2 * n[35:] / 128), rtol=1e-6)  # the slow ones divided by the factor
    cos, sin = gpt2_model._rope_tables(128, 40, 10000, rope=rope)
    angle = np.arange(40)[:, None] * np.concatenate([want, want])[None, :]
    np.testing.assert_allclose(np.asarray(cos), np.cos(angle) * rope.attention_factor, atol=2e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(angle) * rope.attention_factor, atol=2e-5)
    ref_cos, ref_sin = reference.rotary_tables(40, 128, SwaMoEShape.from_yaml({"model_raw": {"config": {
        **TOY, "rope_parameters": {**TOY["rope_parameters"], "full_attention": {**YARN, "original_max_position_embeddings": 8192}}}}}).rotary_of("attn"))
    np.testing.assert_allclose(np.asarray(cos), np.asarray(ref_cos), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin), np.asarray(ref_sin), atol=1e-6)


def test_a_default_rotary_by_kind_is_the_table_of_before():
    for got, want in zip(gpt2_model._rope_tables(48, 64, 10000, rope=RopeSpec("default", 500000.0)), gpt2_model._rope_tables(48, 64, 500000)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------------ against the reference


def logits_of(model, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, t: model.apply(p, {"input_ids": t})["logits"])(params, jnp.asarray(tokens[:, :-1])), np.float32)


@pytest.fixture(scope="module")
def reference_logits(toy, tokens):
    """The reference's logits on the seeded weights, once for the three tests that hold a program against them (PR 44: each
    computed them again, 19 s of the suite's clock a time)."""
    return np.asarray(reference.logits_layer_by_layer(toy[1], SEED, tokens[:, :-1]))


def test_float32_program_is_the_reference_forward(toy, tokens, reference_logits):
    model, shape, params = toy
    want = reference_logits
    assert want.std() > 0.1 and np.abs(logits_of(model, params, tokens) - want).max() < 1e-5


@pytest.mark.parametrize("broken, changes", [("the window dropped", {"sliding_window": 64}),
                                             ("plain rotary on the global layers", {"rope_parameters": {"full_attention": {"rope_type": "default", "rope_theta": 500000}}})])
def test_a_program_without_the_window_or_without_yarn_is_another_model(toy, tokens, reference_logits, broken, changes):
    _, shape, params = toy
    other = build(**changes).with_spec_updates(compute_dtype="float32")
    want = reference_logits
    assert np.abs(logits_of(other, params, tokens) - want).max() > 1e-3, broken


def program_loss(model, params, tokens, with_parts=False):
    """Cross entropy plus the term the layers hand up, as `training/train_step.py` composes them."""
    hidden, counted = model.apply_counted(params, {"input_ids": jnp.asarray(tokens[:, :-1])}, train=True, hidden=True)
    logits = model.head_logits(params, hidden)
    ce = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits), jnp.asarray(tokens[:, 1:])[..., None], axis=-1))
    term = model.loss_from_layers(counted)
    loss = ce if term is None else ce + term
    return (loss, counted) if with_parts else loss


@pytest.mark.parametrize("coef", [0.0, 0.01, 1.0])
def test_loss_and_every_leafs_gradient_are_the_references(toy, tokens, coef):
    """With and without the balance term, and with it as heavy as the cross entropy: the term's own gradient (through
    the mean score of every expert, not through the counts) then carries half the router's."""
    _, _, params = toy
    model = build(moe_config={**MOE, "router_aux_loss_coef": coef}).with_spec_updates(compute_dtype="float32")
    shape = shape_of(moe_config={**MOE, "router_aux_loss_coef": coef})
    with jax.default_matmul_precision("highest"):
        (loss, counted), grads = jax.jit(jax.value_and_grad(lambda p: program_loss(model, p, tokens, True), has_aux=True))(params)
        ref_params = reference.reference_params(shape, seed_key(SEED))
        (want, (ce, aux)), want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.batch_loss(p, jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:]), shape, with_parts=True), has_aux=True))(ref_params)
    assert abs(float(loss) - float(want)) < 2e-5 * abs(float(want))
    assert abs(float(counted["moe_aux_loss"]) - float(aux)) < 1e-5 and 1.0 <= float(aux) < 4.0, "1 at balance, E / k where k experts take all"
    assert (model.loss_from_layers(counted) is None) == (coef == 0.0)
    got_leaves, want_leaves = reference_layout(grads), want_grads
    for r, (got_run, want_run) in enumerate(zip(got_leaves["runs"], want_leaves["runs"])):
        for name, want_leaf in want_run.items():
            scale = float(jnp.abs(want_leaf).max())
            assert scale > 0 and float(jnp.abs(got_run[name] - want_leaf).max()) < 2e-4 * scale, (r, name)
    for name in ("wte", "lm_head", "final_norm"):
        assert float(jnp.abs(got_leaves[name] - want_leaves[name]).max()) < 2e-4 * float(jnp.abs(want_leaves[name]).max()), name


def test_the_layer_by_layer_gradient_is_the_whole_models(tokens):
    """`gradient_stream` (what the benchmark follows the program with) computes what `jax.grad` of `batch_loss` computes."""
    shape = shape_of(moe_config={**MOE, "router_aux_loss_coef": 1.0})
    key = seed_key(SEED)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    layers = [reference.reference_layer(shape, key, i) for i in range(shape.n_layer)]
    params = reference.reference_params(shape, key)
    outer = {name: params[name] for name in reference.OUTER}
    loss, (grads, outer_grads), (ce, aux, loads) = reference.loss_and_gradients(shape, layers, outer, inputs, targets)
    # one jitted program (PR 44: op by op this call took most of the test's 38 s)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: reference.batch_loss(p, jnp.asarray(inputs), jnp.asarray(targets), shape)))(params)
    assert abs(loss - float(want)) < 1e-5 and loss == pytest.approx(ce + aux, abs=1e-6) and loads.shape == (8, 16) and loads.sum() == 8 * 2 * 64 * 4
    named = reference.by_run(shape, grads, outer_grads)
    for r, run in enumerate(want_grads["runs"]):
        for name, leaf in run.items():
            assert float(jnp.abs(named[f"run{r}.{name}"] - leaf).max()) < 1e-3 * max(float(jnp.abs(leaf).max()), 1e-6), (r, name)  # float32 sums in another order


def test_the_shares_parts_add_up_to_the_uncut_layer(toy):
    """The guide's share test: four layers that each hold a quarter of the 16 experts (0-3, 4-7, 8-11, 12-15) give parts of
    the routed sum that add up to what the uncut reference layer gives, and each counts the pairs of its own experts."""
    model, shape, _ = toy
    whole = dataclasses.replace(shape, experts_held=16, expert_offset=0)
    w = {k: v.astype(jnp.float32) for k, v in layer_weights(whole, seed_key(SEED), 1).items()}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 64, 128)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, load, _ = jax.jit(jax.vmap(lambda row: reference.expert_layer(row, w, whole)))(x)  # jitted, as each share's below (PR 44: the suite's clock)
        total, held = jnp.zeros_like(want), []
        for offset in (0, 4, 8, 12):
            part = build(moe_config={**MOE, "expert_offset": offset}).with_spec_updates(compute_dtype="float32")
            leaves = {"router": {"kernel": w["router"]}, "experts": {n: w[f"experts_{n}"][offset: offset + 4] for n in ("W", "V", "W_2")}}
            out, counters = jax.jit(lambda leaves, x, part=part: MoE(part.config_spec).apply({"params": leaves}, x))(leaves, x)
            total, held = total + out, held + [float(counters[0])]
            assert np.asarray(counters[3:19]).tolist() == np.asarray(load.sum(axis=0)).tolist(), "every share counts all 16 experts' loads"
    assert float(jnp.abs(total - want).max()) < 1e-5 * float(jnp.abs(want).max())
    assert held == [float(load.sum(axis=0)[o: o + 4].sum()) for o in (0, 4, 8, 12)] and sum(held) == 2 * 64 * 4


# ------------------------------------------------------------------ names on the trace


def test_a_window_layers_attention_and_a_global_layers_carry_names_of_their_own(toy, tokens):
    """`block/window/attn/...` and `block/global/attn/...` on the operations' paths (telemetry/scopes.py), forward and backward,
    with the per-kind rotary tables under `attn/rope`; a model without `layer_types` has neither name."""
    from modalities_tpu.telemetry import scopes

    model, _, params = toy
    lowered = jax.jit(jax.grad(lambda p: program_loss(model, p, tokens))).lower(params)
    text = lowered.as_text(debug_info=True)
    for kind in (scopes.ATTN_WINDOW, scopes.ATTN_GLOBAL):
        assert f"block/{kind}/attn/{scopes.ROPE}" in text and f"block/{kind}/attn/{scopes.ATTN_CORE}" in text and f"block/{kind}/attn/q_attn" in text
        assert f"transpose(jvp(GPT2Module))" in text
    assert "run_0/" in text and "run_3/" in text and f"moe/{scopes.MOE_ROUTER}" in text
    plain = {k: v for k, v in TOY.items() if k not in ("head_dim", "layer_types", "sliding_window", "rope_parameters", "moe_config")}
    dense = GPT2LLM(**GPT2LLMConfig(**plain).model_dump())
    dense_params = jax.eval_shape(lambda: meta.unbox(dense.init_params(jax.random.PRNGKey(0))))
    dense_text = jax.jit(lambda p, t: dense.apply(p, {"input_ids": t})["logits"]).lower(dense_params, jnp.asarray(tokens[:, :-1])).as_text(debug_info=True)
    assert "/window/" not in dense_text and "/global/" not in dense_text and "block/attn/" in dense_text
