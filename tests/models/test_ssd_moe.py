"""The Mamba-2 mixer, attention scaled by a multiplier of its own, the four scalar multipliers and an expert layer that holds a slice
of its shared expert (`model_type: granitemoehybrid`, PR 52) at toy size against the plain reference on seeded weights, in
float32: the mixer and its gradients, the reference with one step of the equations left out as ANOTHER function (so every
step is in the program), the shares of the experts, of the shared expert's width, of the attention's heads and of the
Mamba-2 heads against the uncut layers, the whole stack's loss, counters and gradients, and every refusal by name. One
jitted program a module-scoped fixture wherever a test can share it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.reference import ssd_moe_decoder_f32 as reference
from benchmark.weights_ssd_moe import SsdMoEShape, layer_weights, make_program_tree, reference_layout, seed_key
from modalities_tpu.models.gpt2 import ssd
from modalities_tpu.models.gpt2.gpt2_model import CausalSelfAttention, GPT2LLM, GPT2LLMConfig, refuse_serving
from modalities_tpu.models.gpt2.moe import MoE
from modalities_tpu.telemetry import scopes

SEED = 2**31 + 17
SEQ = 64  # four chunks of 16: a state carried over more than two
norm = lambda dim: {"norm_type": "rms_norm", "config": {"ndim": dim, "bias": False, "epsilon": 1e-5}}  # noqa: E731
MOE = {"n_routed_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 64, "shared_expert_intermediate_size": 128,
       "shared_expert_shards": 4, "scoring_func": "softmax", "topk_method": "greedy", "norm_topk_prob": True, "experts_held": 2,
       "expert_offset": 4, "router_aux_loss_coef": 0.02}
SSD = {"mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_conv_bias": True,
       "mamba_chunk_size": 16, "heads_held": 2}
TYPES = ["mamba", "mamba", "attention", "mamba"]  # two periods' kinds in one stack: three runs
MULTIPLIERS = {"embedding_multiplier": 12.0, "residual_multiplier": 0.22, "attention_multiplier": 1 / 32, "logits_scaling": 16.0}
PUBLISHED = {"num_attention_heads": 8, "num_key_value_heads": 4}  # of which the toy holds 2 on 1, a quarter, as of the Mamba-2 heads and the shared width
TOY = dict(
    sample_key="input_ids", prediction_key="logits", poe_type="NOPE", sequence_length=SEQ, vocab_size=512, n_layer=4,
    n_head_q=2, n_head_kv=1, n_embd=128, head_dim=32, ffn_hidden=384, dropout=0.0, bias=False,
    attention_config={"qkv_transforms": [{"type_hint": "IdentityTransform", "config": {}}]},
    attention_implementation="manual", activation_type="swiglu", attention_norm_config=norm(128), ffn_norm_config=norm(128),
    lm_head_norm_config=norm(128), use_weight_tying=True, moe_config=MOE, layer_types=TYPES, ssd_config=SSD, **MULTIPLIERS,
)
HIGHEST = jax.default_matmul_precision("highest")


def build(**changes) -> GPT2LLM:
    return GPT2LLM(**GPT2LLMConfig(**{**TOY, **changes}).model_dump())


def stirred(params, scale=0.05):
    """Every small leaf (norm leaves, `D`: ones as seeded; `A_log`, `dt_bias`) moved off its seeded value, so that each matters."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    return jax.tree.unflatten(tree, [leaf + scale * jax.random.normal(key, leaf.shape) if leaf.size <= 512 and "conv" not in jax.tree_util.keystr(path)
                                     else leaf for (path, leaf), key in zip(leaves, keys)])


@pytest.fixture(scope="module")
def toy():
    """The model computing in float32, its seeded weights (bfloat16 values, held in float32) with the constants stirred, and their shape."""
    model = build().with_spec_updates(compute_dtype="float32")
    shape = SsdMoEShape.from_yaml({"model_raw": {"config": TOY}, **PUBLISHED})
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


def seeded(shape, layer: int, kind: str) -> dict:
    return {k: v.astype(jnp.float32) for k, v in layer_weights(shape, seed_key(SEED), layer, kind).items()}


# ------------------------------------------------------------------ the tree, the stack, the config


def test_the_stack_is_runs_of_mamba_and_attention_layers_spelled_by_layer_types(toy):
    model, shape, params = toy
    spec = model.config_spec
    assert spec.stack_runs == (("ssd", "moe", 2), ("attn", "moe", 1), ("ssd", "moe", 1)) and shape.runs == (("ssd", 0, 2), ("attn", 2, 1), ("ssd", 3, 1))
    assert spec.counter_row_width == 3 + 16 + 1 + 1 and spec.mixer_counter_names == ("ssd_decay_mean",)
    assert set(model.counted) == {"moe_pairs_held", "moe_load_max", "moe_load_mean", "moe_expert_load", "moe_aux_loss", "ssd_decay_mean"}
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == shape.all_params()
    mixer = params["params"]["run_0"]["blocks"]["block"]["ssd"]
    assert {jax.tree_util.keystr(path): tuple(leaf.shape[1:]) for path, leaf in jax.tree_util.tree_leaves_with_path(mixer)} == {
        "['A_log']": (2,), "['D']": (2,), "['conv_bias']": (64,), "['conv_kernel']": (4, 64), "['dt_bias']": (2,),
        "['in_proj']['kernel']": (128, 32 + 32 + 32 + 2), "['norm_scale']": (32,), "['out_proj']['kernel']": (32, 128)}
    assert params["params"]["run_1"]["blocks"]["block"]["moe"]["shared"]["W"]["kernel"].shape[1:] == (128, 32)  # a quarter of the 128 published
    assert "lm_head" not in params["params"]


def test_initial_values_are_mamba_2s_own_draws(toy):
    fresh = meta.unbox(jax.jit(toy[0].init_params)(jax.random.PRNGKey(3)))["params"]["run_0"]["blocks"]["block"]["ssd"]
    a, dt = np.exp(np.asarray(fresh["A_log"])), np.logaddexp(0.0, np.asarray(fresh["dt_bias"]))
    assert np.all((a >= 1.0) & (a <= 16.0)) and np.all((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001))
    assert np.all(np.asarray(fresh["D"]) == 1.0) and np.all(np.asarray(fresh["norm_scale"]) == 1.0)
    assert np.all(np.abs(np.asarray(fresh["conv_kernel"])) <= 0.5) and np.all(np.abs(np.asarray(fresh["conv_bias"])) <= 0.5)


def test_weight_decay_spares_the_table_the_norms_and_the_mixers_vectors(toy):
    from modalities_tpu.optimizers.optimizer_factory import build_weight_decay_mask

    model, _, params = toy
    mask = reference_layout(build_weight_decay_mask(params, model, ["embedding", "norm", "ssd_vectors"]))
    spared = {name for run in mask["runs"] for name, on in run.items() if not on} | {name for name in reference.OUTER if not mask[name]}
    assert spared == set(reference.NOT_DECAYED)
    assert all(mask["runs"][0][name] for name in ("in_proj", "out_proj", "router", "experts_W", "shared_W_2")) and mask["runs"][1]["c_proj"]


REFUSED = [
    ({"ssd_config": None}, "go together"), ({"layer_types": ["attention"] * 4}, "go together"),
    ({"layer_types": ["mamba", "mamba", "full_attention", "mamba"]}, "mamba and attention layers stand beside each other"),
    ({"layer_types": ["attention", "sliding_attention"] * 2, "ssd_config": None, "sliding_window": 16}, "mamba and attention layers stand beside each other or alone"),
    ({"ssd_config": {**SSD, "mamba_n_groups": 2}}, "mamba_n_groups"), ({"ssd_config": {**SSD, "heads_held": 9}}, "exceeds mamba_n_heads"),
    ({"ssd_config": {**SSD, "mamba_expand": 2}}, "mamba_expand"),
    ({"attn_layer_period": 2, "ssm_config": {"d_state": 8}}, "ssd_config beside attn_layer_period, ssm_config"),
    ({"mla_config": {"q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16}, "n_head_kv": 2}, "mla_config"),
    ({"loop_config": {"total_ut_steps": 2}, "moe_config": None}, "loop_config"),
    ({"sliding_window": 16}, "ssd_config beside sliding_window"),
    ({"scale_residual_merge": True}, "scale_residual_merge"),
    ({"moe_config": {**MOE, "shared_expert_shards": 3}}, "does not divide the shared expert's width"),
]


@pytest.mark.parametrize("changes, match", REFUSED, ids=[match for _, match in REFUSED])
def test_what_is_not_written_is_refused_at_config_time(changes, match):
    with pytest.raises((ValueError, Exception), match=match):
        build(**changes)


def test_serving_cp_tp_and_pp_are_refused_by_what_is_missing(toy, tokens, monkeypatch):
    from modalities_tpu.parallel import sharding

    model, _, params = toy
    with pytest.raises(NotImplementedError, match=r"convolution's last mamba_d_conv - 1 inputs .* \[heads, mamba_d_head, mamba_d_state\] state"):
        refuse_serving(model.config_spec)
    with pytest.raises(NotImplementedError, match="do not carry the scores' scale"):
        refuse_serving(dataclasses.replace(model.config_spec, ssd=None, layer_kinds=(), moe=None))
    with pytest.raises(NotImplementedError, match="convolution's last"):
        model.init_decode_cache(params, 1)
    ids = {"input_ids": tokens[:, :-1]}
    other = build().with_spec_updates(compute_dtype="float32", context_parallel_axis="cp")
    with pytest.raises(NotImplementedError, match="state's hand-off along the cp axis"):
        jax.eval_shape(lambda p: other.apply(p, ids), params)
    dense = build(ssd_config=None, layer_types=None, moe_config=None).with_spec_updates(pipeline_axis="pp")
    with pytest.raises(NotImplementedError, match="stage functions of its own"):
        jax.eval_shape(lambda: dense.module.init(jax.random.PRNGKey(0), tokens[:, :-1]))
    monkeypatch.setattr(sharding, "installed_axis_size", lambda name: 2 if name == "tp" else 1)
    with pytest.raises(NotImplementedError, match="gated norm's mean square would run across the shards"):
        jax.eval_shape(lambda p: model.apply(p, ids), params)


def test_a_model_without_such_a_layer_imports_nothing_of_it():
    import subprocess
    import sys

    code = ("import sys; from tests.models.test_gdn_moe import build; build(); "
            "assert not [m for m in sys.modules if m.endswith(('gpt2.ssd', 'ops.ssd'))], 'imported'")
    subprocess.run([sys.executable, "-c", code], check=True, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "PYTHONPATH": "."})


# ------------------------------------------------------------------ the Mamba-2 mixer


@pytest.fixture(scope="module")
def mixer(toy):
    """The program's mixer and the reference's on the same leaves and input, each one jitted program: outputs, gradients, counters."""
    model, shape, params = toy
    h = jnp.asarray(np.random.default_rng(1).normal(size=(2, SEQ, 128)), jnp.float32)
    leaves, w = block_of(params, 0)["ssd"], layer_of(params, 0)
    probe = jnp.asarray(np.random.default_rng(2).normal(size=h.shape), jnp.float32)
    apply = lambda leaves, h: ssd.Mamba2Mixer(model.config_spec).apply({"params": leaves}, h)  # noqa: E731
    ref = lambda w, h, skip: jax.vmap(lambda row: reference.ssd_mixer(row, w, shape, skip))(h)  # noqa: E731
    with HIGHEST:
        got, counted = jax.jit(apply)(leaves, h)
        got_grads = jax.jit(jax.grad(lambda l, h: jnp.sum(apply(l, h)[0] * probe), argnums=(0, 1)))(leaves, h)
        want_grads = jax.jit(jax.grad(lambda w, h: jnp.sum(ref(w, h, reference.NONE) * probe), argnums=(0, 1)))(w, h)
        parts = jax.jit(jax.vmap(lambda row: reference.ssd_parts(row, w, shape)))(h)
    return dict(h=h, w=w, leaves=leaves, got=got, counted=counted, got_grads=got_grads, want_grads=want_grads, ref=jax.jit(ref), parts=parts)


def test_the_whole_mixer_and_its_gradients_are_the_references(mixer):
    want = mixer["ref"](mixer["w"], mixer["h"], reference.NONE)
    assert float(jnp.abs(want).max()) > 0.01 and float(jnp.abs(mixer["got"] - want).max()) < 2e-5 * float(jnp.abs(want).max())
    (got_dw, got_dh), (want_dw, want_dh) = mixer["got_grads"], mixer["want_grads"]
    assert float(jnp.abs(got_dh - want_dh).max()) < 2e-4 * float(jnp.abs(want_dh).max())
    named = {"in_proj": got_dw["in_proj"]["kernel"], "out_proj": got_dw["out_proj"]["kernel"], "conv": got_dw["conv_kernel"], "conv_bias": got_dw["conv_bias"],
             "A_log": got_dw["A_log"], "D": got_dw["D"], "dt_bias": got_dw["dt_bias"], "gate_norm": got_dw["norm_scale"]}
    for name, got_leaf in named.items():
        scale = float(jnp.abs(want_dw[name]).max())
        assert scale > 0 and float(jnp.abs(got_leaf - want_dw[name]).max()) < 5e-4 * scale, name


def test_the_mixer_counts_its_mean_decay(mixer):
    parts = mixer["parts"]
    assert float(mixer["counted"][0]) == pytest.approx(float(jnp.mean(jnp.exp(parts["a"]))), rel=1e-5)
    assert 0.0 < float(mixer["counted"][0]) < 1.0 and float(jnp.max(parts["a"])) < 0.0


@pytest.mark.parametrize("without", ["decay", "skip_d", "conv_silu", "gate", "gate_norm", "dt_softplus"])
def test_a_mixer_with_a_step_left_out_is_another_mixer(mixer, without):
    """At Mamba-2's own draws (dt 0.001 to 0.1: a mean decay of 0.98 a token, and a state that adds a hundredth to the skip's `D x` on a
    row of 64) the decay moves the output by a hundredth, every other step by far more; a walk that overflows is another mixer too."""
    want = mixer["ref"](mixer["w"], mixer["h"], reference.skip_flags(without))
    assert not float(jnp.abs(mixer["got"] - want).max()) <= (0.005 if without == "decay" else 0.02) * float(jnp.abs(mixer["got"]).max()), without


# ------------------------------------------------------------------ the shares against the uncut layers


def test_the_shares_of_the_experts_and_of_the_shared_width_add_up_to_the_uncut_layer(toy):
    """The guide's share test, (a): eight layers that each hold two of the 16 experts (offsets 0, 2, ... 14) and one of the four slices of
    the shared expert's width give parts whose routed halves, and the four slices counted ONCE each, add up to what the uncut reference
    gives for the whole expert layer (all 16 experts, the shared expert at its published 128)."""
    model, shape, params = toy
    whole = dataclasses.replace(shape, experts_held=16, expert_offset=0, shared_shards=1)
    slices = [seeded(dataclasses.replace(shape, share=i), 2, "attn") for i in range(4)]  # a slice is drawn by its index among the four
    w = {**seeded(dataclasses.replace(whole, shared_shards=4), 2, "attn"),
         "shared_W": jnp.concatenate([s["shared_W"] for s in slices], axis=1), "shared_V": jnp.concatenate([s["shared_V"] for s in slices], axis=1),
         "shared_W_2": jnp.concatenate([s["shared_W_2"] for s in slices], axis=0)}
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, SEQ, 128)), jnp.float32)
    with HIGHEST:
        want, load, _ = jax.jit(jax.vmap(lambda row: reference.expert_layer(row, w, whole)))(x)
        slice_alone = jax.jit(jax.vmap(lambda row, s: reference.swiglu(row, s["shared_W"], s["shared_V"], s["shared_W_2"]), in_axes=(0, None)))
        load = load.sum(axis=0)
        part = build(moe_config={**MOE, "expert_offset": 0}).with_spec_updates(compute_dtype="float32")
        # ONE compiled program for the eight shares: a share's offset is static, so the share at offset 0 is handed the router's
        # columns and the experts' stacks rolled until ITS two experts come first (the loads come out rolled with them)
        one_share = jax.jit(lambda leaves, x: MoE(part.config_spec).apply({"params": leaves}, x))
        total, held = sum(slice_alone(x, s) for s in slices), []
        for offset in range(0, 16, 2):
            mine = slices[(offset // 2) % 4]
            leaves = {"router": {"kernel": jnp.roll(w["router"], -offset, axis=1)}, "shared": {n: {"kernel": mine[f"shared_{n}"]} for n in ("W", "V", "W_2")},
                      "experts": {n: jnp.roll(w[f"experts_{n}"], -offset, axis=0)[:2] for n in ("W", "V", "W_2")}}
            out, counters = one_share(leaves, x)
            total, held = total + (out - slice_alone(x, mine)), held + [float(counters[0])]
            assert np.roll(np.asarray(counters[3:19]), offset).tolist() == np.asarray(load).tolist(), "every share counts all 16 experts' loads"
    assert float(jnp.abs(total - want).max()) < 2e-5 * float(jnp.abs(want).max())
    assert held == [float(load[o: o + 2].sum()) for o in range(0, 16, 2)] and sum(held) == 2 * SEQ * 4


def test_the_four_shares_of_the_attentions_heads_add_up_to_the_uncut_attention(toy):
    """(b): 2 query heads on 1 key/value head, four times, against 8 on 4 in one layer; the scores scaled by the multiplier, not 1 / sqrt(32)."""
    model, shape, _ = toy
    whole = dataclasses.replace(shape, n_head_q=8, n_head_kv=4)
    h = jnp.asarray(np.random.default_rng(6).normal(size=(2, SEQ, 128)), jnp.float32)
    reference_attention = jax.jit(lambda w, h, skip: jax.vmap(lambda row: reference.attention(row, w, whole, skip))(h))
    one_share = jax.jit(lambda leaves, h: CausalSelfAttention(model.config_spec).apply({"params": leaves}, h))
    with HIGHEST:
        w = seeded(whole, 2, "attn")
        want = reference_attention(w, h, reference.NONE)
        total = sum(one_share({n: {"kernel": seeded(dataclasses.replace(shape, share=i), 2, "attn")[n]} for n in ("q_attn", "k_attn", "v_attn", "c_proj")}, h)
                    for i in range(4))
        other = reference_attention(w, h, reference.skip_flags("attention_multiplier"))
    assert float(jnp.abs(total - want).max()) < 2e-5 * float(jnp.abs(want).max())
    assert float(jnp.abs(total - other).max()) > 0.02 * float(jnp.abs(want).max()), "the multiplier is in it"


def test_the_four_shares_of_the_mamba_heads_give_the_uncut_mixers_y_and_differ_from_it_in_the_norm_alone(toy):
    """(c): four mixers of 2 heads against one of 8. Before the gated norm every share's `y` is the uncut mixer's, head for head (B and
    C are computed alike by every share). THE ONE PLACE A SHARE DEPARTS FROM THE WHOLE is the gated norm: its mean square runs over
    the 32 channels a share holds, not over all 128. So the shares' outputs add up to an uncut mixer whose norm runs over each
    share's channels apart, and NOT to the published one, whose norm runs over all of them."""
    model, shape, _ = toy
    whole = dataclasses.replace(shape, heads_held=8)
    h = jnp.asarray(np.random.default_rng(8).normal(size=(2, SEQ, 128)), jnp.float32)
    one_share = jax.jit(lambda leaves, h: ssd.Mamba2Mixer(model.config_spec).apply({"params": leaves}, h, mutable=["intermediates"]))
    from benchmark.weights_ssd_moe import _program_mixer

    with HIGHEST:
        w = seeded(whole, 0, "ssd")
        parts = jax.jit(jax.vmap(lambda row: reference.ssd_parts(row, w, whole)))(h)
        total = 0.0
        for i in range(4):
            (out, _), kept = one_share(_program_mixer(seeded(dataclasses.replace(shape, share=i), 0, "ssd"), "ssd"), h)
            np.testing.assert_allclose(kept["intermediates"]["y"][0], parts["y"][:, :, 2 * i: 2 * i + 2], atol=2e-5 * float(jnp.abs(parts["y"]).max()))
            total = total + out
        g = parts["g"].reshape(2, SEQ, 4, 32)
        apart = (g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + 1e-5)).reshape(2, SEQ, 128) * w["gate_norm"]
        apart = jnp.einsum("bsf,fe->bse", apart, w["out_proj"])
    assert float(jnp.abs(total - apart).max()) < 2e-5 * float(jnp.abs(apart).max())
    assert float(jnp.abs(total - parts["out"]).max()) > 0.02 * float(jnp.abs(parts["out"]).max()), "the published norm runs over all 128 channels"


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
    ids, targets = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    with HIGHEST:
        (loss, counted), grads = jax.jit(jax.value_and_grad(lambda p: program_loss(model.with_spec_updates(remat_variant="full"), p, tokens), has_aux=True))(params)
        reference_loss = jax.jit(lambda p, skip: reference.batch_loss(p, ids, targets, shape, True, skip))
        (want, parts), want_grads = jax.jit(jax.value_and_grad(lambda p: reference.batch_loss(p, ids, targets, shape, True), has_aux=True))(reference_layout(params))
    return loss, counted, grads, want, parts, want_grads, reference_loss


def test_loss_counters_and_every_leafs_gradient_are_the_references(stack, toy):
    loss, counted, grads, want, (ce, aux, loads), want_grads, _ = stack
    shape = toy[1]
    assert abs(float(loss) - float(want)) < 2e-5 * abs(float(want))
    assert np.asarray(counted["moe_expert_load"]).tolist() == np.asarray(loads).tolist() and float(counted["moe_aux_loss"]) == pytest.approx(float(aux), rel=1e-5)
    assert float(counted["moe_pairs_held"]) == pytest.approx(reference.pairs_held(shape, np.asarray(loads)))
    assert 0.0 < float(counted["ssd_decay_mean"]) < 1.0
    got_leaves = reference_layout(grads)
    for r, run in enumerate(want_grads["runs"]):
        for name, want_leaf in run.items():
            scale = float(jnp.abs(want_leaf).max())
            assert scale > 0 and float(jnp.abs(got_leaves["runs"][r][name] - want_leaf).max()) < 1e-3 * scale, (r, name)
    for name in reference.OUTER:
        assert float(jnp.abs(got_leaves[name] - want_grads[name]).max()) < 1e-3 * float(jnp.abs(want_grads[name]).max()), name


@pytest.fixture(scope="module")
def loud(toy, tokens):
    """The toy with its final norm's scale at 256: at the seeded 1 the logits are within 0.1 of each other and the loss within float32's
    resolution of ln(512) whatever the layers do. The sound program's loss on it."""
    model, _, params = toy
    params = {"params": {**params["params"], "lm_head_norm": {"scale": 256.0 * params["params"]["lm_head_norm"]["scale"]}}}
    with HIGHEST:
        return params, float(jax.jit(lambda p: program_loss(model, p, tokens)[0])(params))


SEEN_IN_THE_GRADIENT = {"attention_multiplier": (1, "q_attn"), "gate_renorm": (0, "experts_W_2")}  # (run, leaf)


@pytest.mark.parametrize("name", [*MULTIPLIERS, "gate_renorm"])
def test_a_multiplier_moved_to_where_it_would_not_be_there_changes_the_loss(stack, toy, loud, tokens, name):
    """Each of the four, set to what the model would compute without it (1, or 1 / sqrt(head_dim) for the scores), is another program,
    and the reference with that step left out is the same other program; the gates not renormalised over the chosen are another too.
    Two of the five move this toy's loss by less than float32 resolves (one attention layer of four, behind c_proj's scaled draw; gates
    that sum to 0.3 and not 1 on experts behind theirs): they are seen in the loss's gradient, on the leaf next to the step."""
    (params, loss), reference_loss = loud, stack[6]
    with HIGHEST:
        assert float(reference_loss(reference_layout(params), reference.NONE)[0]) == pytest.approx(loss, rel=2e-5)
        want = float(reference_loss(reference_layout(params), reference.skip_flags(name))[0])
        if name in SEEN_IN_THE_GRADIENT:
            run, leaf = SEEN_IN_THE_GRADIENT[name]
            ids, targets = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
            other = jax.jit(jax.grad(lambda p: reference.batch_loss(p, ids, targets, toy[1], False, reference.skip_flags(name))))(reference_layout(toy[2]))
            sound = stack[5]["runs"][run][leaf]
            assert float(jnp.abs(other["runs"][run][leaf] - sound).max()) > 0.05 * float(jnp.abs(sound).max()), name
        else:
            assert abs(want - loss) > 2e-4 * abs(loss), name
        if name in MULTIPLIERS:
            moved = build(**{name: 32 ** -0.5 if name == "attention_multiplier" else 1.0}).with_spec_updates(compute_dtype="float32")
            assert float(jax.jit(lambda p: program_loss(moved, p, tokens)[0])(params)) == pytest.approx(want, rel=2e-5)


def test_the_mixers_counter_is_the_mean_over_the_mamba_layers_alone(stack):
    """Three Mamba-2 layers and one attention layer: the attention layer's row holds a zero where a Mamba-2 layer's holds its mean decay,
    and the published mean leaves that row out."""
    counted = stack[1]
    assert counted["moe_expert_load"].shape == (4, 16) and 0.5 < float(counted["ssd_decay_mean"]) < 1.0


def test_the_new_scopes_the_plan_and_the_counter_are_on_the_step(toy, tokens):
    import json
    import tempfile
    from pathlib import Path

    from modalities_tpu.telemetry import Telemetry, set_active_telemetry

    model, _, params = toy
    with tempfile.TemporaryDirectory() as folder:
        telemetry = Telemetry(output_folder_path=Path(folder))
        previous = set_active_telemetry(telemetry)
        try:
            text = jax.jit(jax.grad(lambda p: program_loss(model, p, tokens)[0])).lower(params).as_text(debug_info=True)
        finally:
            set_active_telemetry(previous)
        events = [json.loads(line) for line in Path(telemetry.sink_path).read_text().splitlines() if line.strip()]
    for path in ("ssd/in_proj", "ssd/conv", "ssd/scan/intra", "ssd/scan/state", "ssd/gate", "ssd/out_proj"):
        assert f"/{path}/" in text, path
    assert set(scopes.SSD_SCOPES) == {"in_proj", "conv", "scan", "intra", "state", "gate", "out_proj"}
    plan = next(e for e in events if e.get("name") == "ssd_plan")
    assert (plan["heads"], plan["heads_held"], plan["chunk"], plan["chunks"], plan["form"]) == (8, 2, 16, 4, "chunked_jnp")
    assert plan["state_bytes_a_layer"] == 2 * 4 * 2 * 16 * 16 * 4
