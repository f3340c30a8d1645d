"""A decoder with latent attention (models/gpt2/mla.py) and a routed-and-shared expert layer
told which experts it holds (models/gpt2/moe.py), held to the plain reference
(benchmark/reference/moe_mla_decoder_f32.py) on the benchmark's seeded weights at toy
widths: d 128, 4 heads of 32 + 16 for q and k and 32 for v, a latent of 64; 8 experts of 64,
3 a token, 4 held from the third, 1 shared; 1 dense + 2 expert layers."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.core import meta

from benchmark.reference import moe_mla_decoder_f32 as reference
from benchmark.weights_moe import MoEMLAShape, layer_weights, make_program_tree, reference_layout, seed_key
from modalities_tpu.models.gpt2.gpt2_model import GPT2LLM, GPT2LLMConfig
from modalities_tpu.models.gpt2.mla import LatentAttention, interleaved_rope
from modalities_tpu.models.gpt2.moe import MoE
from tests.models.test_gpt2_model import tiny_gpt2

SEED = 2**31 + 7
NORM = {"norm_type": "rms_norm", "config": {"ndim": 128, "bias": False, "epsilon": 1e-6}}
MLA = {"kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32, "rope_theta": 1e6}
MOE = {"n_routed_experts": 8, "num_experts_per_tok": 3, "moe_intermediate_size": 64, "n_shared_experts": 1,
       "first_k_dense_replace": 1, "routed_scaling_factor": 2.448, "experts_held": 4, "expert_offset": 2}
TOY = dict(
    sample_key="input_ids", prediction_key="logits", poe_type="NOPE", sequence_length=64, vocab_size=512, n_layer=3,
    n_head_q=4, n_head_kv=4, n_embd=128, ffn_hidden=384, dropout=0.0, bias=False,
    attention_config={"qkv_transforms": [{"type_hint": "IdentityTransform", "config": {}}]},
    attention_implementation="manual", activation_type="swiglu", attention_norm_config=NORM, ffn_norm_config=NORM,
    lm_head_norm_config=NORM, use_weight_tying=False, mla_config=MLA, moe_config=MOE,
)
HYPER = {"lr": [1e-3, 1e-3, 1e-3], "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}


def build(**changes) -> GPT2LLM:
    return GPT2LLM(**GPT2LLMConfig(**{**TOY, **changes}).model_dump())


def unboxed_shapes(model):
    return jax.eval_shape(lambda: meta.unbox(model.init_params(jax.random.PRNGKey(0))))


@pytest.fixture(scope="module")
def toy():
    """The model computing in float32, its seeded weights (bfloat16 values, held in float32), and their shape."""
    model = build().with_spec_updates(compute_dtype="float32")
    shape = MoEMLAShape.from_yaml({"model_raw": {"config": TOY}})
    params = make_program_tree(shape, SEED, unboxed_shapes(model), match_dtypes=False)
    return model, shape, jax.tree.map(lambda x: x.astype(jnp.float32), params)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 511, size=(2, 65)).astype(np.int32)


def logits_of(model, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, t: model.apply(p, {"input_ids": t})["logits"])(params, jnp.asarray(tokens[:, :-1])), np.float32)


@pytest.fixture(scope="module")
def float32_logits(toy, tokens):
    model, _, params = toy
    return logits_of(model, params, tokens)


def layer_leaves(shape, layer: int, kind: str) -> dict:
    return {k: v.astype(jnp.float32) for k, v in layer_weights(shape, seed_key(SEED), layer, kind).items()}


# ------------------------------------------------------------------ config


def test_the_stack_has_runs_by_mixer_and_feed_forward(toy):
    model, shape, params = toy
    spec = model.config_spec
    assert spec.ffn_kinds == ("mlp", "moe", "moe") and spec.stack_runs == (("attn", "mlp", 1), ("attn", "moe", 2)) and spec.has_moe
    assert spec.runs == (("attn", 3),), "the mixer alone has one kind"
    assert hash(spec) == hash(build().with_spec_updates(compute_dtype="float32").config_spec)
    assert sorted(params["params"]) == ["lm_head", "lm_head_norm", "run_0", "run_1", "wte"]
    block = params["params"]["run_1"]["blocks"]["block"]
    assert sorted(block["attn"]) == ["c_proj", "kv_a_norm", "kv_a_proj", "kv_b_proj", "q_proj"]
    assert sorted(block["moe"]) == ["experts", "router", "shared"] and block["moe"]["experts"]["W"].shape == (2, 4, 128, 64)
    assert block["moe"]["router"]["kernel"].shape == (2, 128, 8), "the router keeps its width whatever is held"
    assert model.counted == {"moe_pairs_held": (), "moe_load_max": (), "moe_load_mean": (), "moe_expert_load": (2, 8)}
    assert tiny_gpt2("manual").counted == {}


@pytest.mark.parametrize("block, key, value, match", [
    ("mla_config", "q_lora_rank", 64, "q_lora_rank"), ("mla_config", "rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("moe_config", "n_group", 2, "n_group"), ("moe_config", "topk_group", 2, "topk_group"),
    ("moe_config", "scoring_func", "tanh", "scoring_func"), ("moe_config", "expert_offset", 6, "exceeds n_routed_experts"),
])
def test_what_is_not_written_is_refused_at_config_time(block, key, value, match):
    with pytest.raises(ValueError, match=match):
        GPT2LLMConfig(**{**TOY, block: {**TOY[block], key: value}})


def test_latent_attention_refuses_a_second_rotary_and_unequal_heads():
    rotary = {"qkv_transforms": [{"type_hint": "RotaryTransform", "config": {"n_embd": 128, "n_head": 4}}]}
    with pytest.raises(ValueError, match="its own rotary"):
        GPT2LLMConfig(**{**TOY, "attention_config": rotary})
    with pytest.raises(ValueError, match="n_head_kv must equal n_head_q"):
        GPT2LLMConfig(**{**TOY, "n_head_kv": 2})


# ------------------------------------------------------------------ against the reference


def test_latent_attention_is_the_references(toy):
    model, shape, params = toy
    w = layer_leaves(shape, 1, "moe")
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 64, 128)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = LatentAttention(model.config_spec).apply({"params": jax.tree.map(lambda v: v[0], params["params"]["run_1"]["blocks"]["block"]["attn"])}, x)
        want = jax.vmap(lambda row: reference.latent_attention(row, w, shape))(x)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-5


def test_the_expert_layer_is_the_references(toy):
    model, shape, params = toy
    w = layer_leaves(shape, 1, "moe")
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 64, 128)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, counters = MoE(model.config_spec).apply({"params": jax.tree.map(lambda v: v[0], params["params"]["run_1"]["blocks"]["block"]["moe"])}, x)
        want, load = jax.vmap(lambda row: reference.expert_layer(row, w, shape))(x)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-5
    held = load.sum(axis=0)[2:6]  # experts 2 to 5 are held
    assert float(counters[0]) == float(held.sum()) > 0 and float(counters[2]) == float(held.sum()) / 4
    assert float(counters[1]) == float(held.max()) and np.asarray(counters[3:]).tolist() == np.asarray(load.sum(axis=0)).tolist()


def test_float32_program_is_the_reference_forward(toy, tokens, float32_logits):
    _, shape, _ = toy
    want = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens[:, :-1]))
    assert want.std() > 0.1 and np.abs(float32_logits - want).max() < 1e-5


def test_bfloat16_program_is_near_the_reference_forward(toy, tokens):
    """The program as it trains computes its blocks in bfloat16: about three digits. With logits of standard deviation
    0.25 the two differ by up to 0.008 here (read on the CPU, PR 30); a dropped rotary or shared expert moves them by 0.1."""
    _, shape, params = toy
    want = np.asarray(reference.logits_layer_by_layer(shape, SEED, tokens[:, :-1]))
    assert np.abs(logits_of(build(), params, tokens) - want).max() < 0.03


@pytest.mark.parametrize("combine", ["gathers", "kernel_interpreted"])
def test_loss_and_every_gradient_leaf(toy, tokens, combine, monkeypatch, request):
    """Off the chip the sum by token is the k gathers; inside the tests' seam the model takes what a TPU takes, interpreted:
    the kernel `moe_combine` over the 128 tokens as two blocks of 64 (and with it the flash kernels and the fused norms): the
    same loss and gradients against the reference, under the same limits."""
    from modalities_tpu.ops import expert_dispatch

    if combine == "kernel_interpreted":
        request.getfixturevalue("kernels_interpreted")
        monkeypatch.setattr(expert_dispatch, "COMBINE_BLOCK", 64)
    assert expert_dispatch.combine_form(128, 3, 4, 128) == ("slabs" if combine == "kernel_interpreted" else "gathers")
    model, shape, params = toy
    ids, targets = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])

    def loss(p):
        logits = model.apply(p, {"input_ids": ids})["logits"]
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, targets))

    with jax.default_matmul_precision("highest"):
        got_loss, got = jax.jit(jax.value_and_grad(loss))(params)
        want_loss, want = jax.jit(jax.value_and_grad(lambda p: reference.batch_loss(p, ids, targets, shape)))(
            reference.reference_params(shape, seed_key(SEED)))
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    got = reference_layout(got)
    leaves = [(f"run{r}.{name}", got["runs"][r][name], run[name]) for r, run in enumerate(want["runs"]) for name in run]
    leaves += [(name, got[name], want[name]) for name in reference.OUTER]
    assert len(leaves) == 10 + 15 + 3
    for name, g, w in leaves:
        if name.endswith("router_bias"):
            assert float(jnp.abs(g).max()) == 0.0 == float(jnp.abs(w).max()), "only the choice's indices depend on b"
            continue
        assert float(jnp.abs(w).max()) > 0, name
        assert float(jnp.abs(g - w).max() / jnp.abs(w).max()) < 2e-4, name


def test_three_adamw_steps_with_the_mask_leave_b_alone_and_b_matters(toy, tokens):
    """The program's own optimizer (AdamW, the configuration's decay mask, clipping) beside the reference's three steps;
    `b` comes out as it went in, and a program that ignores it routes otherwise."""
    from modalities_tpu.optimizers.optimizer_factory import build_weight_decay_mask

    model, shape, params = toy
    rng = np.random.default_rng(5)
    batches = [(s[:, :-1], s[:, 1:]) for s in (rng.integers(0, 511, size=(2, 65)).astype(np.int32) for _ in range(3))]
    mask = build_weight_decay_mask(params, model, ["embedding", "norm", "router_bias"])
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, mask=mask))

    def loss(p, ids, targets):
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(model.apply(p, {"input_ids": ids})["logits"], targets))

    @jax.jit
    def step(p, state, ids, targets):
        value, grads = jax.value_and_grad(loss)(p, ids, targets)
        updates, state = tx.update(grads, state, p)
        return optax.apply_updates(p, updates), state, value

    p, state, losses = params, tx.init(params), []
    with jax.default_matmul_precision("highest"):
        for ids, targets in batches:
            p, state, value = step(p, state, jnp.asarray(ids), jnp.asarray(targets))
            losses.append(float(value))
        want = reference.train_steps(shape, SEED, batches, HYPER)
    assert losses == pytest.approx(want["losses"], abs=2e-5)
    moved = jax.device_get(reference.leaf_norms(jax.tree.map(lambda a, b: a - b, reference_layout(p), reference_layout(params))))
    for name, norms in want["delta_norms"].items():
        np.testing.assert_allclose(moved[name], norms, rtol=2e-3, atol=1e-7, err_msg=name)
    bias = lambda tree: tree["params"]["run_1"]["blocks"]["block"]["moe"]["router"]["e_score_correction_bias"]  # noqa: E731
    assert bool(jnp.array_equal(bias(p), bias(params))) and float(jnp.abs(bias(params)).max()) > 0
    without = jax.tree_util.tree_map_with_path(
        lambda path, v: jnp.zeros_like(v) if "e_score_correction_bias" in jax.tree_util.keystr(path) else v, params)
    ids = {"input_ids": jnp.asarray(batches[0][0])}
    assert float(jnp.abs(model.apply(params, ids)["logits"] - model.apply(without, ids)["logits"]).max()) > 1e-3
    assert not mask["params"]["run_1"]["blocks"]["block"]["moe"]["router"]["e_score_correction_bias"]
    named = reference_layout(mask)
    for r, run in enumerate(named["runs"]):
        for name, decayed in run.items():
            assert decayed == (name not in reference.NOT_DECAYED), (r, name)
    assert named["lm_head"] and not named["wte"] and not named["final_norm"]


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """8 experts over 4 shares of 2: the four layers' outputs, the shared expert counted once, sum to the uncut
    reference's output for the whole layer, values and the gradient to x."""
    model, shape, _ = toy
    whole = dataclasses.replace(shape, experts_held=8, expert_offset=0)
    w = {k: v.astype(jnp.float32) for k, v in layer_weights(whole, seed_key(SEED), 1, "moe").items()}
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 64, 128)), jnp.float32)
    direction = jnp.asarray(np.random.default_rng(4).normal(size=(1, 64, 128)), jnp.float32)

    def share(x, offset):
        spec = dataclasses.replace(model.config_spec, moe=dataclasses.replace(model.config_spec.moe, experts_held=2, expert_offset=offset))
        p = {"router": {"kernel": w["router"], "e_score_correction_bias": w["router_bias"]},
             "experts": {n: w[f"experts_{n}"][offset:offset + 2] for n in ("W", "V", "W_2")},
             "shared": {n: {"kernel": w[f"shared_{n}"]} for n in ("W", "V", "W_2")}}
        routed_and_shared, _ = MoE(spec).apply({"params": p}, x)
        shared_alone, _ = MoE(spec).apply({"params": {**p, "experts": jax.tree.map(jnp.zeros_like, p["experts"])}}, x)
        return routed_and_shared, shared_alone

    def summed(x):
        parts = [share(x, offset) for offset in (0, 2, 4, 6)]
        return sum(both - shared for both, shared in parts) + parts[0][1]  # the shared expert once

    with jax.default_matmul_precision("highest"):
        # each side's value and pullback one jitted program (PR 44: the suite's clock)
        got, (dx,) = jax.jit(lambda x, d: (lambda out, pull: (out, pull(d)))(*jax.vjp(summed, x)))(x, direction)
        want, (dx_ref,) = jax.jit(lambda x, d: (lambda out, pull: (out, pull(d)))(*jax.vjp(lambda x: reference.expert_layer(x[0], w, whole)[0][None], x)))(x, direction)
        assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-5
    assert float(jnp.abs(dx - dx_ref).max() / jnp.abs(dx_ref).max()) < 1e-5


# ------------------------------------------------------------------ rotary


def test_interleaved_rotary_is_hugging_faces_move_then_rotate_halves_in_the_scores():
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 1, 16)), jnp.float32)
    ours = jnp.einsum("shd,tgd->hst", interleaved_rope(q, 1e6)[0], interleaved_rope(k, 1e6)[0])
    theirs = jnp.einsum("shd,tgd->hst", reference.rope_move_then_rotate_halves(q[0], 1e6), reference.rope_move_then_rotate_halves(k[0], 1e6))
    np.testing.assert_allclose(ours, theirs, atol=2e-5)
    # the same members, moved: evens to the front half, odds to the back
    moved = reference.rope_move_then_rotate_halves(q[0], 1e6)
    np.testing.assert_allclose(jnp.concatenate([interleaved_rope(q, 1e6)[0][..., 0::2], interleaved_rope(q, 1e6)[0][..., 1::2]], -1), moved, atol=2e-6)
    # scores depend on the distance between positions alone: the same offset on all changes nothing
    shifted = jnp.einsum("shd,tgd->hst", interleaved_rope(q, 1e6, offset=37)[0], interleaved_rope(k, 1e6, offset=37)[0])
    np.testing.assert_allclose(ours, shifted, atol=2e-4)
    assert float(jnp.abs(interleaved_rope(q, 1e6, offset=37) - interleaved_rope(q, 1e6)).max()) > 0.1


# ------------------------------------------------------------------ the stack


def test_the_runs_give_the_logits_of_the_same_layers_unrolled(toy, tokens, float32_logits):
    _, _, params = toy
    unrolled = build().with_spec_updates(compute_dtype="float32", scan_layers=False)
    p, flat, layer = params["params"], {}, 0
    for run in ("run_0", "run_1"):
        stacked = p[run]["blocks"]["block"]
        for k in range(jax.tree.leaves(stacked)[0].shape[0]):
            flat[f"h_{layer}"] = jax.tree.map(lambda x: x[k], stacked)
            layer += 1
    flat.update(wte=p["wte"], lm_head_norm=p["lm_head_norm"], lm_head=p["lm_head"])
    assert jax.tree.map(jnp.shape, {"params": flat}) == jax.tree.map(lambda s: s.shape, unboxed_shapes(unrolled))
    np.testing.assert_allclose(float32_logits, logits_of(unrolled, {"params": flat}, tokens), atol=2e-6)
    _, counted = unrolled.apply_counted({"params": flat}, {"input_ids": jnp.asarray(tokens[:, :-1])})
    _, scanned = build().with_spec_updates(compute_dtype="float32").apply_counted(params, {"input_ids": jnp.asarray(tokens[:, :-1])})
    assert {k: np.asarray(v).tolist() for k, v in counted.items()} == {k: np.asarray(v).tolist() for k, v in scanned.items()}
    assert counted["moe_expert_load"].shape == (2, 8) and float(counted["moe_expert_load"].sum()) == 2 * 2 * 64 * 3
    # a model that counts nothing gives an empty dict beside what `apply` gives, through the same entry
    dense = tiny_gpt2("manual")
    dense_params = meta.unbox(dense.init_params(jax.random.PRNGKey(0)))
    batch = {"input_ids": jnp.asarray(tokens[:, :32] % 127)}
    out, nothing = dense.apply_counted(dense_params, batch)
    assert nothing == {} and bool(jnp.array_equal(out["logits"], dense.apply(dense_params, batch)["logits"]))
    assert dense.after_update(dense_params, {}) is dense_params


@pytest.mark.parametrize("scan_layers", [True, False])
def test_the_selection_bias_moves_by_the_sign_of_the_load_error(toy, tokens, scan_layers):
    """`after_update` is DeepSeek-V3's rule on every expert layer's `b`, from the loads the pass counted over all 8 experts
    (`test_the_expert_layer_is_the_references` holds a layer's to the reference's count); speed 0 returns the tree as it is."""
    _, shape, params = toy
    if not scan_layers:
        p = params["params"]
        params = {"params": {**{f"h_{i}": jax.tree.map(lambda x, k=k: x[k], p[run]["blocks"]["block"])
                                for i, (run, k) in enumerate((("run_0", 0), ("run_1", 0), ("run_1", 1)))},
                             "wte": p["wte"], "lm_head_norm": p["lm_head_norm"], "lm_head": p["lm_head"]}}
    model = build(moe_config={**MOE, "bias_update_speed": 0.01}).with_spec_updates(compute_dtype="float32", scan_layers=scan_layers)
    still = build().with_spec_updates(compute_dtype="float32", scan_layers=scan_layers)
    ids = {"input_ids": jnp.asarray(tokens[:, :-1])}
    _, counted = model.apply_counted(params, ids)
    assert still.after_update(params, counted) is params
    moved = model.after_update(params, counted)
    if scan_layers:
        bias = lambda tree: np.asarray(tree["params"]["run_1"]["blocks"]["block"]["moe"]["router"]["e_score_correction_bias"])  # noqa: E731
    else:
        bias = lambda tree: np.stack([np.asarray(tree["params"][f"h_{i}"]["moe"]["router"]["e_score_correction_bias"]) for i in (1, 2)])  # noqa: E731
    load = np.asarray(counted["moe_expert_load"])
    assert load.sum(axis=1).tolist() == [2 * 64 * 3] * 2
    np.testing.assert_allclose(bias(moved) - bias(params), 0.01 * np.sign(load.mean(axis=1, keepdims=True) - load), atol=1e-7)
    assert np.abs(bias(moved) - bias(params)).max() > 0
    others = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), moved, params)
    assert sum(not same for same in jax.tree.leaves(others)) == (1 if scan_layers else 2), "only the biases moved"


def test_one_run_of_attention_and_mlp_is_the_dense_decoder_bitwise():
    """Neither block set: the dense decoder's tree and logits, bit for bit; the spec's new fields change no program."""
    dense = tiny_gpt2("pytorch_flash")
    assert dense.config_spec.stack_runs == (("attn", "mlp", 2),) and not dense.config_spec.has_moe and dense.config_spec.mla is None
    params = meta.unbox(dense.init_params(jax.random.PRNGKey(0)))
    assert sorted(params["params"]) == ["blocks", "lm_head_norm", "wte"]
    assert sorted(params["params"]["blocks"]["block"]) == ["attention_norm", "attn", "ffn_norm", "mlp"]
    batch = {"input_ids": jnp.asarray(np.random.default_rng(0).integers(0, 127, size=(2, 32)), jnp.int32)}
    again = tiny_gpt2("pytorch_flash")
    assert bool(jnp.array_equal(dense.apply(params, batch)["logits"], again.apply(params, batch)["logits"]))


@pytest.mark.parametrize("entry", ["init_decode_cache", "init_slot_cache", "init_paged_cache"])
@pytest.mark.parametrize("lacking, match", [({"moe_config": None}, "latent cache"), ({"mla_config": None}, "decode path through the dispatch")])
def test_serving_refuses_by_name_of_what_is_missing(entry, lacking, match):
    model = build(**lacking)
    params = unboxed_shapes(model)
    args = {"init_decode_cache": (params, 1), "init_slot_cache": (params, 2), "init_paged_cache": (params, 4, 16)}[entry]
    with pytest.raises(NotImplementedError, match=match):
        getattr(model, entry)(*args)


def test_mfu_calculator_counts_active_parameters_and_two_head_sizes():
    from modalities_tpu.utils.mfu import GPT2MFUCalculator

    model = build()
    mfu = GPT2MFUCalculator(n_layer=3, sequence_length=64, n_embd=128, world_size=1, wrapped_model=model)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(unboxed_shapes(model)))
    expert = 3 * 128 * 64
    assert mfu.num_parameters == n and mfu.active_parameters == n - 2 * (4 - 3 * 4 / 8) * expert, "3 of 8 chosen, 4 of 8 held: 1.5 of the 4 held a token"
    assert mfu.attention_width == 4 * (48 + 32), "q k^T at 48 and p v at 32, not 2 x n_embd"
    assert mfu.compute(1000.0) == pytest.approx(1000.0 * (6 * mfu.active_parameters + 6 * 3 * 64 * 320) / 1e12)
    dense = GPT2MFUCalculator(n_layer=2, sequence_length=32, n_embd=64, world_size=1, wrapped_model=tiny_gpt2("manual"))
    assert dense.active_parameters == dense.num_parameters and dense.attention_width == 128, "the dense formula, 6N + 12 L s h"
