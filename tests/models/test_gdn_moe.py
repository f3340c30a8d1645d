"""The gated delta rule's mixer, the gated attention with its quarter-head rotary, zero-centred norms and the gated shared
expert (`model_type: qwen3_next`, PR 44) at toy size against the plain reference on seeded weights, in float32: each
sub-layer and its gradients, the reference with one step of the equations left out as ANOTHER function (so every step
is in the program), the eight shares of an expert layer against the uncut one, the whole stack's loss, counters and
gradients, and every refusal by name. One jitted program a module-scoped fixture wherever a test can share it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.reference import gdn_moe_decoder_f32 as reference
from benchmark.weights_gdn_moe import GdnMoEShape, layer_weights, make_program_tree, reference_layout, seed_key
from modalities_tpu.models.gpt2 import gdn
from modalities_tpu.models.gpt2.gpt2_model import CausalSelfAttention, GPT2LLM, GPT2LLMConfig, refuse_serving
from modalities_tpu.models.gpt2.moe import MoE
from modalities_tpu.telemetry import scopes

SEED = 2**31 + 13
SEQ = 128  # two chunks of 64: a state carried from one to the next
norm = lambda dim: {"norm_type": "rms_norm", "config": {"ndim": dim, "bias": False, "epsilon": 1e-6, "zero_centered": True}}  # noqa: E731
MOE = {"n_routed_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 64, "shared_expert_intermediate_size": 64,
       "shared_expert_gate": True, "scoring_func": "softmax", "topk_method": "greedy", "norm_topk_prob": True, "experts_held": 4,
       "expert_offset": 4, "router_aux_loss_coef": 0.001}
GDN = {"linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 16, "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4}
ROPE = {"full_attention": {"rope_type": "default", "rope_theta": 10000000, "partial_rotary_factor": 0.25}}
TYPES = ["linear_attention", "full_attention"]
TOY = dict(
    sample_key="input_ids", prediction_key="logits", poe_type="NOPE", sequence_length=SEQ, vocab_size=512, n_layer=2,
    n_head_q=4, n_head_kv=2, n_embd=128, head_dim=32, ffn_hidden=384, dropout=0.0, bias=False,
    attention_config={"qkv_transforms": [{"type_hint": "RotaryTransform", "config": {"n_embd": 128, "n_head": 4, "base_freq": 10000000}}],
                      "qk_norm_config": norm(32)},
    attention_implementation="manual", activation_type="swiglu", attention_norm_config=norm(128), ffn_norm_config=norm(128),
    lm_head_norm_config=norm(128), use_weight_tying=False, moe_config=MOE, layer_types=TYPES, rope_parameters=ROPE,
    gdn_config=GDN, attn_output_gate=True,
)
HIGHEST = jax.default_matmul_precision("highest")


def build(**changes) -> GPT2LLM:
    return GPT2LLM(**GPT2LLMConfig(**{**TOY, **changes}).model_dump())


def stirred(params, scale=0.05):
    """Every small leaf (norm leaves, `A_log`, `dt_bias`, the gate: zeros and ones as seeded) moved off its constant, so that each matters."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    return jax.tree.unflatten(tree, [leaf + scale * jax.random.normal(key, leaf.shape) if leaf.size <= 512 and "conv" not in jax.tree_util.keystr(path)
                                     else leaf for (path, leaf), key in zip(leaves, keys)])


@pytest.fixture(scope="module")
def toy():
    """The model computing in float32, its seeded weights (bfloat16 values, held in float32) with the constants stirred, and their shape."""
    model = build().with_spec_updates(compute_dtype="float32")
    shape = GdnMoEShape.from_yaml({"model_raw": {"config": TOY}})
    like = jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))
    params = make_program_tree(shape, SEED, like, match_dtypes=False)
    return model, shape, stirred(jax.tree.map(lambda x: x.astype(jnp.float32), params))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 511, size=(2, SEQ + 1)).astype(np.int32)


def layer_of(params, run: int) -> dict:
    return {name: value[0] for name, value in reference_layout(params)["runs"][run].items()}


def block_of(params, run: int) -> dict:
    return jax.tree.map(lambda v: v[0], params["params"][f"run_{run}"]["blocks"]["block"])


# ------------------------------------------------------------------ the tree, the stack, the config


def test_the_stack_is_a_run_of_rule_layers_then_a_run_of_attention_layers(toy):
    model, shape, params = toy
    spec = model.config_spec
    assert spec.stack_runs == (("gdn", "moe", 1), ("attn", "moe", 1)) and build(n_layer=4, layer_types=TYPES[:1] * 3 + TYPES[1:]).config_spec.stack_runs == (
        ("gdn", "moe", 3), ("attn", "moe", 1))
    assert spec.counter_row_width == 3 + 16 + 1 + 2 and spec.mixer_counters == 2
    assert set(model.counted) == {"moe_pairs_held", "moe_load_max", "moe_load_mean", "moe_expert_load", "moe_aux_loss", "gdn_decay_mean", "gdn_beta_mean"}
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == shape.all_params()
    rule = params["params"]["run_0"]["blocks"]["block"]["gdn"]
    assert {jax.tree_util.keystr(path): tuple(leaf.shape[1:]) for path, leaf in jax.tree_util.tree_leaves_with_path(rule)} == {
        "['A_log']": (4,), "['ba']['kernel']": (128, 2, 4), "['conv_kernel']": (4, 128), "['dt_bias']": (4,), "['out_norm_scale']": (16,),
        "['out_proj']['kernel']": (4, 16, 128), "['qkvz']['kernel']": (128, 2, 96)}
    attn = params["params"]["run_1"]["blocks"]["block"]["attn"]
    assert attn["q_attn"]["kernel"].shape[1:] == (128, 4, 64) and attn["q_norm"]["scale"].shape[1:] == (32,)  # a head's query, then its gate
    assert params["params"]["run_1"]["blocks"]["block"]["moe"]["shared_gate"].shape[1:] == (128, 1)


def test_initial_values_are_the_sources(toy):
    model = toy[0]
    fresh = meta.unbox(jax.jit(model.init_params)(jax.random.PRNGKey(3)))["params"]
    rule = fresh["run_0"]["blocks"]["block"]["gdn"]
    a = np.exp(np.asarray(rule["A_log"]))
    assert np.all((a >= 1.0) & (a <= 16.0)) and np.all(np.asarray(rule["dt_bias"]) == 1.0) and np.all(np.asarray(rule["out_norm_scale"]) == 1.0)
    assert np.all(np.abs(np.asarray(rule["conv_kernel"])) <= 0.5)
    for name in ("attention_norm", "ffn_norm"):  # a zero-centred leaf starts at 0
        assert not np.any(np.asarray(fresh["run_0"]["blocks"]["block"][name]["scale"]))
    assert not np.any(np.asarray(fresh["lm_head_norm"]["scale"])) and not np.any(np.asarray(fresh["run_1"]["blocks"]["block"]["attn"]["q_norm"]["scale"]))


def test_weight_decay_spares_the_vectors_the_taps_and_the_gate(toy):
    from modalities_tpu.optimizers.optimizer_factory import build_weight_decay_mask

    model, _, params = toy
    mask = reference_layout(build_weight_decay_mask(params, model, ["embedding", "norm", "gdn_vectors", "shared_expert_gate"]))
    spared = {name for run in mask["runs"] for name, on in run.items() if not on} | {name for name in reference.OUTER if not mask[name]}
    assert spared == set(reference.NOT_DECAYED)
    assert all(mask["runs"][0][name] for name in ("qkvz", "ba", "out_proj", "router", "experts_W", "shared_W_2")) and mask["lm_head"]


REFUSED = [
    ({"gdn_config": None}, "go together"), ({"layer_types": ["full_attention"] * 2}, "go together"),
    ({"layer_types": ["linear_attention", "sliding_attention"], "sliding_window": 16}, "beside sliding_attention"),
    ({"sliding_window": 16}, "gdn_config beside sliding_window"),
    ({"loop_config": {"total_ut_steps": 2}, "moe_config": None}, "loop_config"),
    ({"cca_config": {"cca_time0": 2, "cca_time1": 2}}, "cca_config"),
    ({"ssm_config": {"d_state": 8}}, "ssm_config"),
    ({"mla_config": {"q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16}}, "mla_config"),
    ({"gdn_config": {**GDN, "linear_num_value_heads": 3}}, "multiple of linear_num_key_heads"),
    ({"rope_parameters": {"full_attention": {**ROPE["full_attention"], "partial_rotary_factor": 0.1}}}, "must be even"),
    ({"moe_config": {**MOE, "shared_expert_intermediate_size": None}}, "gates a shared expert"),
    ({"moe_config": {**MOE, "n_shared_experts": 1}}, "set one"),
    ({"attention_norm_config": {"norm_type": "rms_norm", "config": {"ndim": 128, "bias": True, "zero_centered": True}}}, "zero_centered"),
]


@pytest.mark.parametrize("changes, match", REFUSED, ids=[match for _, match in REFUSED])
def test_what_is_not_written_is_refused_at_config_time(changes, match):
    with pytest.raises((ValueError, Exception), match=match):
        build(**changes)


def test_serving_cp_and_an_uneven_tp_axis_are_refused_by_what_is_missing(toy, tokens, monkeypatch):
    from modalities_tpu.parallel import sharding

    model, _, params = toy
    with pytest.raises(NotImplementedError, match="convolution's last linear_conv_kernel_dim - 1 inputs"):
        refuse_serving(model.config_spec)
    with pytest.raises(NotImplementedError, match="do not carry the gate"):
        refuse_serving(dataclasses.replace(model.config_spec, gdn=None, layer_kinds=(), moe=None))
    with pytest.raises(NotImplementedError, match="convolution's last"):
        model.init_decode_cache(params, 1)
    other = build().with_spec_updates(compute_dtype="float32", context_parallel_axis="cp")
    with pytest.raises(NotImplementedError, match="hand-off along the cp axis"):
        jax.eval_shape(lambda p: other.apply(p, {"input_ids": tokens[:, :-1]}), params)
    monkeypatch.setattr(sharding, "installed_axis_size", lambda name: 4 if name == "tp" else 1)
    with pytest.raises(NotImplementedError, match="a tp axis of 4 does not divide"):
        jax.eval_shape(lambda p: model.apply(p, {"input_ids": tokens[:, :-1]}), params)
    monkeypatch.setattr(sharding, "installed_axis_size", lambda name: 2 if name == "tp" else 1)
    jax.eval_shape(lambda p: model.apply(p, {"input_ids": tokens[:, :-1]}), params)  # 2 divides 2 key heads and 2 key/value heads


# ------------------------------------------------------------------ a zero-centred norm


@pytest.mark.parametrize("rank", [3, 4])
def test_a_zero_centred_norm_is_one_plus_w_in_both_forms(kernels_interpreted_off_and_on, rank):
    from modalities_tpu.models.components.layer_norms import NormSpec, build_norm

    spec = NormSpec.from_wrapper_config(norm(128), 128)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 128) if rank == 3 else (2, 16, 4, 128)) * 3.0
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (128,))
    written_out = lambda x, w: x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * (1.0 + w)  # noqa: E731
    module = build_norm(spec, "n")
    assert not np.any(np.asarray(module.init(jax.random.PRNGKey(2), x)["params"]["scale"]))
    apply = lambda x, w: module.apply({"params": {"scale": w}}, x)  # noqa: E731
    np.testing.assert_allclose(apply(x, w), written_out(x, w), atol=2e-6)
    probe = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    for got, want in zip(jax.grad(lambda x, w: jnp.sum(apply(x, w) * probe), argnums=(0, 1))(x, w),
                         jax.grad(lambda x, w: jnp.sum(written_out(x, w) * probe), argnums=(0, 1))(x, w)):
        np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    unset = NormSpec.from_wrapper_config({"norm_type": "rms_norm", "config": {"ndim": 128, "bias": False}}, 128)
    assert not unset.zero_centered and np.all(np.asarray(build_norm(unset, "n").init(jax.random.PRNGKey(2), x)["params"]["scale"]) == 1.0)


@pytest.fixture(params=["reference_form", "kernel_interpreted"])
def kernels_interpreted_off_and_on(request):
    from modalities_tpu.ops import tiers

    if request.param == "reference_form":
        yield
    else:
        with tiers.interpreted_kernels():
            yield


# ------------------------------------------------------------------ the rule's mixer


@pytest.fixture(scope="module")
def mixer(toy):
    """The program's mixer and the reference's on the same leaves and input, each one jitted program: outputs, gradients, counters."""
    model, shape, params = toy
    h = jnp.asarray(np.random.default_rng(1).normal(size=(2, SEQ, 128)), jnp.float32)
    leaves, w = block_of(params, 0)["gdn"], layer_of(params, 0)
    probe = jnp.asarray(np.random.default_rng(2).normal(size=h.shape), jnp.float32)
    apply = lambda leaves, h: gdn.GatedDeltaNet(model.config_spec).apply({"params": leaves}, h)  # noqa: E731
    ref = lambda w, h, skip: jax.vmap(lambda row: reference.gdn_mixer(row, w, shape, skip))(h)  # noqa: E731
    with HIGHEST:
        got, counted = jax.jit(apply)(leaves, h)
        got_grads = jax.jit(jax.grad(lambda l, h: jnp.sum(apply(l, h)[0] * probe), argnums=(0, 1)))(leaves, h)
        ref_jit = jax.jit(ref)
        want_grads = jax.jit(jax.grad(lambda w, h: jnp.sum(ref(w, h, reference.NONE) * probe), argnums=(0, 1)))(w, h)
        parts = jax.jit(jax.vmap(lambda row: reference.gdn_parts(row, w, shape)))(h)
    return dict(h=h, w=w, leaves=leaves, got=got, counted=counted, got_grads=got_grads, want_grads=want_grads, ref=ref_jit, parts=parts)


def test_the_whole_mixer_and_its_gradients_are_the_references(mixer):
    want = mixer["ref"](mixer["w"], mixer["h"], reference.NONE)
    assert float(jnp.abs(want).max()) > 0.01 and float(jnp.abs(mixer["got"] - want).max()) < 2e-5 * float(jnp.abs(want).max())
    (got_dw, got_dh), (want_dw, want_dh) = mixer["got_grads"], mixer["want_grads"]
    assert float(jnp.abs(got_dh - want_dh).max()) < 2e-4 * float(jnp.abs(want_dh).max())
    named = {"qkvz": got_dw["qkvz"]["kernel"], "ba": got_dw["ba"]["kernel"], "out_proj": got_dw["out_proj"]["kernel"], "conv": got_dw["conv_kernel"],
             "A_log": got_dw["A_log"], "dt_bias": got_dw["dt_bias"], "out_norm": got_dw["out_norm_scale"]}
    for name, got_leaf in named.items():
        scale = float(jnp.abs(want_dw[name]).max())
        # the decay's two leaves reach the output through exp(g) with g about -1 to -20: their gradients are sums of terms near underflow
        assert scale > 0 and float(jnp.abs(got_leaf - want_dw[name]).max()) < (5e-3 if name in ("A_log", "dt_bias") else 5e-4) * scale, name


def test_the_mixer_counts_its_mean_decay_and_mean_beta(mixer):
    parts = mixer["parts"]
    assert float(mixer["counted"][0]) == pytest.approx(float(jnp.mean(jnp.exp(parts["g"]))), rel=1e-5)
    assert float(mixer["counted"][1]) == pytest.approx(float(jnp.mean(parts["beta"])), rel=1e-5)
    assert 0.0 < float(mixer["counted"][0]) < 1.0 and float(jnp.max(parts["g"])) < 0.0


def test_each_step_of_the_mixers_equations_alone(mixer, toy):
    """The program's own pieces on the reference's inputs: the convolution with its SiLU, the L2 norms with q's scale, and the
    chunked rule on the reference's q, k, v, g and beta against the reference's recurrence on them."""
    from modalities_tpu.ops.gated_delta_rule import gated_delta_rule
    from modalities_tpu.ops.selective_scan import causal_depthwise_conv

    shape, w, parts = toy[1], mixer["w"], mixer["parts"]
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, SEQ, shape.conv_width)), jnp.float32)
    np.testing.assert_allclose(jax.nn.silu(causal_depthwise_conv(x, w["conv"])), jax.vmap(lambda row: jax.nn.silu(reference.depthwise_conv(row, w["conv"])))(x), atol=1e-5)
    raw = jnp.asarray(np.random.default_rng(5).normal(size=(2, SEQ, 2, 16)), jnp.float32)
    np.testing.assert_allclose(gdn.l2_normalised(raw, 16 ** -0.5), raw * jax.lax.rsqrt(jnp.sum(raw * raw, -1, keepdims=True) + 1e-6) / 4.0, atol=1e-6)
    with HIGHEST:  # the reference's q and k are repeated to the value heads already: every second one is a key head's own
        o = gated_delta_rule(parts["q"][:, :, ::2], parts["k"][:, :, ::2], parts["v"], parts["g"], parts["beta"])
    np.testing.assert_allclose(o, parts["o"], atol=2e-5 * float(jnp.abs(parts["o"]).max()))


@pytest.mark.parametrize("without", ["decay", "beta", "qk_l2norm", "conv_silu"])
def test_a_mixer_with_a_step_left_out_is_another_mixer(mixer, without):
    want = mixer["ref"](mixer["w"], mixer["h"], reference.skip_flags(without))
    assert float(jnp.abs(mixer["got"] - want).max()) > 0.02 * float(jnp.abs(mixer["got"]).max()), without


# ------------------------------------------------------------------ the gated attention, the gated shared expert


@pytest.fixture(scope="module")
def attention(toy):
    model, shape, params = toy
    h = jnp.asarray(np.random.default_rng(6).normal(size=(2, SEQ, 128)), jnp.float32)
    leaves, w = block_of(params, 1)["attn"], layer_of(params, 1)
    with HIGHEST:
        got = jax.jit(lambda leaves, h: CausalSelfAttention(model.config_spec).apply({"params": leaves}, h))(leaves, h)
        ref = jax.jit(lambda w, h, skip: jax.vmap(lambda row: reference.gated_attention(row, w, shape, skip))(h))
    return got, ref, w, h


def test_the_gated_attention_with_its_quarter_head_rotary_is_the_references(attention, toy):
    got, ref, w, h = attention
    want = ref(w, h, reference.NONE)
    assert toy[1].rotary_dim == 8 and float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("without", ["attn_gate", "partial_rotary"])
def test_an_attention_without_its_gate_or_with_the_whole_head_turned_is_another(attention, without):
    got, ref, w, h = attention
    assert float(jnp.abs(got - ref(w, h, reference.skip_flags(without))).max()) > 0.02 * float(jnp.abs(got).max()), without


def test_the_shares_parts_add_up_to_the_uncut_layer(toy):
    """The guide's share test: eight layers that each hold two of the 16 experts (offsets 0, 2, ... 14) give parts of the
    expert sub-layer whose routed halves add up to what the uncut reference gives, the gated shared expert counted ONCE
    (every share computes it whole: under expert parallelism one chip's would be kept, or each an eighth of its width)."""
    model, shape, params = toy
    whole = dataclasses.replace(shape, experts_held=16, expert_offset=0)
    w = {**layer_of(params, 1), **{k: v.astype(jnp.float32) for k, v in layer_weights(whole, seed_key(SEED), 1, "attn").items() if k.startswith("experts_")}}
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, SEQ, 128)), jnp.float32)
    moe_leaves = block_of(params, 1)["moe"]
    with HIGHEST:
        want, load, _ = jax.jit(jax.vmap(lambda row: reference.expert_layer(row, w, whole)))(x)
        shared = jax.jit(jax.vmap(lambda row: reference.shared_expert(row, w)))(x)
        bare = jax.jit(jax.vmap(lambda row: reference.shared_expert(row, w, reference.skip_flags("shared_gate"))))(x)
        load = load.sum(axis=0)
        part = build(moe_config={**MOE, "experts_held": 2, "expert_offset": 0}).with_spec_updates(compute_dtype="float32")
        # ONE compiled program for the eight shares: a share's offset is static, so the share at offset 0 is handed the router's
        # columns and the experts' stacks rolled until ITS two experts come first (the loads come out rolled with them)
        one_share = jax.jit(lambda leaves, x: MoE(part.config_spec).apply({"params": leaves}, x))
        total, held = jnp.zeros_like(want), []
        for offset in range(0, 16, 2):
            leaves = {"router": {"kernel": jnp.roll(w["router"], -offset, axis=1)}, "shared": moe_leaves["shared"], "shared_gate": moe_leaves["shared_gate"],
                      "experts": {n: jnp.roll(w[f"experts_{n}"], -offset, axis=0)[:2] for n in ("W", "V", "W_2")}}
            out, counters = one_share(leaves, x)
            total, held = total + (out - shared), held + [float(counters[0])]
            assert np.roll(np.asarray(counters[3:19]), offset).tolist() == np.asarray(load).tolist(), "every share counts all 16 experts' loads"
    assert float(jnp.abs(total + shared - want).max()) < 2e-5 * float(jnp.abs(want).max())
    assert held == [float(load[o: o + 2].sum()) for o in range(0, 16, 2)] and sum(held) == 2 * SEQ * 4
    assert float(jnp.abs(shared - bare).max()) > 0.05 * float(jnp.abs(bare).max()), "the gate is in it"


# ------------------------------------------------------------------ the stack


def program_loss(model, params, tokens):
    """Cross entropy plus the layers' term, as `training/train_step.py` composes them."""
    hidden, counted = model.apply_counted(params, {"input_ids": jnp.asarray(tokens[:, :-1])}, train=True, hidden=True)
    logits = model.head_logits(params, hidden)
    ce = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits), jnp.asarray(tokens[:, 1:])[..., None], axis=-1))
    return ce + model.loss_from_layers(counted), counted


@pytest.fixture(scope="module")
def stack(toy, tokens):
    model, shape, params = toy
    with HIGHEST:
        (loss, counted), grads = jax.jit(jax.value_and_grad(lambda p: program_loss(model.with_spec_updates(remat_variant="full"), p, tokens), has_aux=True))(params)
        (want, parts), want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.batch_loss(p, jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:]), shape, True), has_aux=True))(reference_layout(params))
    return loss, counted, grads, want, parts, want_grads


def test_loss_counters_and_every_leafs_gradient_are_the_references(stack, toy):
    loss, counted, grads, want, (ce, aux, loads), want_grads = stack
    shape = toy[1]
    assert abs(float(loss) - float(want)) < 2e-5 * abs(float(want))
    assert np.asarray(counted["moe_expert_load"]).tolist() == np.asarray(loads).tolist() and float(counted["moe_aux_loss"]) == pytest.approx(float(aux), rel=1e-5)
    assert float(counted["moe_pairs_held"]) == pytest.approx(reference.pairs_held(shape, np.asarray(loads)))
    assert 0.0 < float(counted["gdn_decay_mean"]) < 1.0 and 0.0 < float(counted["gdn_beta_mean"]) < 1.0
    got_leaves = reference_layout(grads)
    for r, run in enumerate(want_grads["runs"]):
        for name, want_leaf in run.items():
            scale = float(jnp.abs(want_leaf).max())
            if name in ("A_log", "dt_bias"):  # 2e-7 here, sums of terms near underflow: the mixer's own test holds them, at a probe that reaches them
                continue
            assert scale > 0 and float(jnp.abs(got_leaves["runs"][r][name] - want_leaf).max()) < 1e-3 * scale, (r, name)
    for name in reference.OUTER:
        assert float(jnp.abs(got_leaves[name] - want_grads[name]).max()) < 1e-3 * float(jnp.abs(want_grads[name]).max()), name


def test_under_full_remat_a_block_that_keeps_the_rules_o_and_group_states_gives_the_loss_and_gradients_it_gave(stack, toy, tokens):
    """PR 48: what the block keeps (`spec.remat_keep_rule`, the plan's to set) are the arrays its recomputed forward made, so loss and every
    gradient stand; and the traced program holds the rule's `intra` twice (the forward pass, the rule's own backward) where it held it three times."""
    from tests.ops.test_gated_delta_rule import programs

    model, _, params = toy
    loss, _, grads = stack[:3]
    keeping = build().with_spec_updates(compute_dtype="float32", remat_variant="full", remat_keep_rule=True)
    step = jax.value_and_grad(lambda p, model: program_loss(model, p, tokens), has_aux=True)
    with HIGHEST:
        (kept_loss, _), kept_grads = jax.jit(lambda p: step(p, keeping))(params)
    assert float(kept_loss) == pytest.approx(float(loss), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(kept_grads), jax.tree.leaves(grads)):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max()) + 1e-8, jax.tree_util.keystr(path)  # `A_log`, `dt_bias`: 2e-7, sums of terms near underflow

    def intra_products(model):
        names = [str(e.source_info.name_stack) for e in programs(jax.make_jaxpr(lambda p: step(p, model))(params).jaxpr, []) if e.primitive.name == "dot_general"]
        return sum("intra" in name and "transpose" not in name for name in names)

    recomputing = build().with_spec_updates(compute_dtype="float32", remat_variant="full")
    plain = build().with_spec_updates(compute_dtype="float32")
    assert intra_products(recomputing) * 2 == intra_products(keeping) * 3 == intra_products(plain) * 3 > 0


def test_the_rule_layers_counters_are_the_mean_over_the_rule_layers_alone(stack):
    """One rule layer and one attention layer: the attention layer's row holds zeros where the rule layer's holds its two, and
    the published mean leaves that row out (`beta` is a sigmoid of small numbers: about a half, not the quarter a mean over
    both rows would read)."""
    counted = stack[1]
    assert float(counted["gdn_beta_mean"]) == pytest.approx(0.5, abs=0.1) and 0.0 < float(counted["gdn_decay_mean"]) < 1.0
    assert counted["moe_expert_load"].shape == (2, 16)


def test_the_new_scopes_and_the_plan_are_on_the_step(toy, tokens):
    from modalities_tpu.telemetry import Telemetry, set_active_telemetry
    import json
    import tempfile
    from pathlib import Path

    model, _, params = toy
    with tempfile.TemporaryDirectory() as folder:
        telemetry = Telemetry(output_folder_path=Path(folder))
        previous = set_active_telemetry(telemetry)
        try:
            text = jax.jit(jax.grad(lambda p: program_loss(model, p, tokens)[0])).lower(params).as_text(debug_info=True)
        finally:
            set_active_telemetry(previous)
        events = [json.loads(line) for line in Path(telemetry.sink_path).read_text().splitlines() if line.strip()]
    for name in scopes.GDN_SCOPES:
        assert f"gdn/{name}/" in text or f"/{name}/" in text or f"jvp({name})/" in text, name  # `group`: in the rule's backward alone, under `jax.vjp`'s transforms
    # a group's `intra` lies inside the scan over the groups since PR 48, one group or several: the scan's body is a function of its own in this text
    for path in ("gdn/rule/state/while/body/closed_call", '"intra/', "gdn/rule/state", "gdn/in_proj/qkvz", "gdn/out/out_proj", "attn/gate", "moe/shared_gate"):
        assert path in text, path
    plan = next(e for e in events if e.get("name") == "gdn_plan" and e["tokens"] == 2 * SEQ)
    assert plan["chunk"] == 64 and plan["chunks"] == 2 and (plan["key_heads"], plan["value_heads"], plan["key_dim"], plan["value_dim"]) == (2, 4, 16, 16)
    assert plan["state_bytes_a_layer"] == 2 * 4 * 16 * 16 * 4 and plan["inverse"] == "nilpotent_product" and "rematerialized groups" in plan["backward"] and plan["kernels"] == [] and plan["norm_kernels"] == []  # heads of 16: the plain walk and the plain norms


@pytest.mark.parametrize("interpreted", [False, True], ids=["plain_walk", "kernels_interpreted"])
def test_the_plan_names_the_walks_kernels_and_the_backward_they_make(interpreted):
    """`gdn_plan` off a trace of the mixer at heads of 128 x 128 (nothing compiles): no kernel off a TPU; where kernels run the walk's two
    under `kernels` and the two norms' four under `norm_kernels` (PR 46: `gdn/qk_norm` forward and backward, then `gdn/out_norm`'s)."""
    import contextlib
    import json
    import tempfile
    from pathlib import Path

    from modalities_tpu.ops import tiers
    from modalities_tpu.telemetry import Telemetry, set_active_telemetry

    spec = build(gdn_config={**GDN, "linear_key_head_dim": 128, "linear_value_head_dim": 128}).config_spec
    with tempfile.TemporaryDirectory() as folder, (tiers.interpreted_kernels() if interpreted else contextlib.nullcontext()):
        telemetry = Telemetry(output_folder_path=Path(folder))
        previous = set_active_telemetry(telemetry)
        try:
            jax.eval_shape(lambda h: gdn.GatedDeltaNet(spec).init_with_output(jax.random.PRNGKey(0), h)[0], jax.ShapeDtypeStruct((1, SEQ, 128), jnp.bfloat16))
        finally:
            set_active_telemetry(previous)
        plan = next(json.loads(line) for line in Path(telemetry.sink_path).read_text().splitlines() if '"gdn_plan"' in line)
    assert (plan["key_dim"], plan["value_dim"]) == (128, 128) and "a state a group kept, a group's matrices computed again" in plan["backward"]
    if interpreted:
        assert plan["kernels"] == ["gated_delta_state_fwd", "gated_delta_state_bwd"] and "the states a chunk kept in VMEM" in plan["backward"]
        assert plan["norm_kernels"] == ["head_l2_norm_fwd", "head_l2_norm_bwd", "gated_head_rms_norm_fwd", "gated_head_rms_norm_bwd"]
    else:
        assert plan["kernels"] == [] and plan["norm_kernels"] == [] and "autodiff over a rematerialized chunk step" in plan["backward"]


def test_the_required_operations_count_what_a_token_passes():
    from modalities_tpu.utils.mfu import GPT2MFUCalculator, gdn_rule_flops_per_token

    model = build(n_layer=4, layer_types=TYPES[:1] * 3 + TYPES[1:])
    spec = model.config_spec
    calc = GPT2MFUCalculator(4, SEQ, 128, 1, wrapped_model=model)
    expert = 3 * 128 * 64
    assert calc.active_parameters == calc.num_parameters - 4 * (4 - 4 * 4 / 16) * expert
    assert calc.rule_flops_per_token == 3 * gdn_rule_flops_per_token(spec.gdn) and calc.n_attention_layer == 1 and calc.attention_width == 2 * 4 * 32
    c, dk, dv = 64, 16, 16
    assert gdn_rule_flops_per_token(spec.gdn) == 4 * (2 * 2 * c * c * dk / 2 + 11 * 2 * c ** 3 + 2 * c * c * (dk + dv) + 6 * c * dk * dv + 2 * c * c * dv) / c


def test_under_a_mesh_the_mixers_norms_and_walk_run_per_shard_and_the_mixer_is_the_plain_one():
    """dp_shard 2 x tp 2 on the CPU's virtual devices, heads of 128 x 128 (2 key and 4 value heads: one key head a shard), kernels interpreted:
    the two norms go through `per_shard` as the walk does (four `shard_map`s of kernels in the forward's trace: q's and k's norm, the walk,
    the gated norm), `out_norm_scale` whole on every shard; the output is the same mixer's traced plain on one device."""
    from modalities_tpu.ops import tiers
    from modalities_tpu.parallel.sharding import activation_rules, default_logical_axis_rules
    from modalities_tpu.running_env.device_mesh import get_device_mesh

    spec = build(gdn_config={**GDN, "linear_key_head_dim": 128, "linear_value_head_dim": 128}).config_spec
    module = gdn.GatedDeltaNet(spec)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 128), jnp.float32)
    params = stirred(meta.unbox(module.init(jax.random.PRNGKey(0), h)))
    want = jax.jit(lambda p, h: module.apply(p, h)[0])(params, h)
    handle = get_device_mesh(device_type="cpu", world_size=4, data_parallel_shard_degree=2, tensor_parallel_degree=2)
    with handle.mesh, activation_rules(default_logical_axis_rules(handle), handle.mesh), tiers.interpreted_kernels():
        text = str(jax.make_jaxpr(lambda p, h: module.apply(p, h)[0])(params, h))
        assert text.count("shard_map") >= 4 and all(name in text for name in ("head_l2_norm_fwd", "gated_head_rms_norm_fwd", "gated_delta_state_fwd"))
        got = jax.jit(lambda p, h: module.apply(p, h)[0])(params, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
